"""Pallas TPU kernel: single-token decode attention over a long KV cache
(flash-decoding style).

Decode shapes (decode_32k / long_500k) are memory-bound: one query row
attends over S cached keys — arithmetic intensity ≈ 1 FLOP/byte, so the
kernel's job is to stream KV at full HBM bandwidth.  The KV sequence is
tiled; a VMEM scratch keeps the running (m, l, acc) and the output is
written on the last tile (same online-softmax recurrence as prefill
flash, with bq=8 query rows — the minimum sublane tile — of which only
the real rows are used).

For sequence-sharded KV (long_500k), each shard runs this kernel over
its local S/shards slice and the partial (m, l, acc) are LSE-merged
across the `model` axis (models/attention.py::merge_partial_attention).
Hence the kernel optionally RETURNS the partials instead of the
normalized output.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import resolve_interpret, NEG_INF, cdiv

__all__ = ["decode_attention_pallas"]


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, m_scr, l_scr, acc_scr,
            *, scale, bk, n_kv_blocks, kv_len, return_partial):
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)                  # (bq, d) — bq=8 sublane pad
    k = k_ref[0].astype(jnp.float32)                  # (bk, d)
    v = v_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * scale  # (bq, bk)
    k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    s = jnp.where(k_pos < kv_len, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    p = jnp.where(jnp.isfinite(s), jnp.exp(s - m_safe), 0.0)
    alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)
    l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = alpha * acc_scr[...] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new

    @pl.when(ki == n_kv_blocks - 1)
    def _finalize():
        l = l_scr[...]
        if return_partial:
            o_ref[0] = acc_scr[...].astype(o_ref.dtype)
            m_ref[0] = m_scr[...].astype(m_ref.dtype)
            l_ref[0] = l.astype(l_ref.dtype)
        else:
            o_ref[0] = (acc_scr[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)
            m_ref[0] = m_scr[...].astype(m_ref.dtype)
            l_ref[0] = l.astype(l_ref.dtype)


def decode_attention_pallas(
    q: jnp.ndarray,        # (B, Hq, D) one new token per sequence
    k: jnp.ndarray,        # (B, Hkv, S, D)
    v: jnp.ndarray,        # (B, Hkv, S, D)
    *,
    scale: float | None = None,
    block_k: int = 512,
    kv_len: int | None = None,
    return_partial: bool = False,
    interpret: bool | None = None,
):
    """Returns (out (B, Hq, D), m (B, Hq, 1), l (B, Hq, 1)); if
    return_partial, ``out`` is the unnormalized accumulator for cross-
    shard LSE merging."""
    interpret = resolve_interpret(interpret)
    b, hq, d = q.shape
    _, hkv, s, _ = k.shape
    group = hq // hkv
    scale = (d ** -0.5) if scale is None else scale
    kv_len = s if kv_len is None else kv_len

    bk = min(block_k, s)
    assert s % bk == 0, "pad KV length to block multiple"
    nk = s // bk

    # Tile q by KV-head group: every row of a (group_p, d) tile shares the
    # same kv head, so the kv BlockSpec is exact for any GQA ratio.
    bq = cdiv(group, 8) * 8                    # sublane-pad the group
    qp = q.reshape(b, hkv, group, d)
    qp = jnp.pad(qp, ((0, 0), (0, 0), (0, bq - group), (0, 0)))
    qp = qp.reshape(b * hkv, bq, d)
    kf = k.reshape(b * hkv, s, d)
    vf = v.reshape(b * hkv, s, d)

    kernel = functools.partial(
        _kernel, scale=scale, bk=bk, n_kv_blocks=nk, kv_len=kv_len,
        return_partial=return_partial,
    )
    out, m, l = pl.pallas_call(
        kernel,
        grid=(b * hkv, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda t, ki: (t, 0, 0)),
            pl.BlockSpec((1, bk, d), lambda t, ki: (t, ki, 0)),
            pl.BlockSpec((1, bk, d), lambda t, ki: (t, ki, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda t, ki: (t, 0, 0)),
            pl.BlockSpec((1, bq, 1), lambda t, ki: (t, 0, 0)),
            pl.BlockSpec((1, bq, 1), lambda t, ki: (t, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * hkv, bq, d), q.dtype),
            jax.ShapeDtypeStruct((b * hkv, bq, 1), jnp.float32),
            jax.ShapeDtypeStruct((b * hkv, bq, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="decode_attention",
    )(qp, kf, vf)

    def unpack(x):
        x = x.reshape(b, hkv, bq, x.shape[-1])[:, :, :group]
        return x.reshape(b, hq, x.shape[-1])

    return unpack(out), unpack(m), unpack(l)
