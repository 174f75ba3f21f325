"""Pallas TPU flash attention (forward), causal + GQA.

Online-softmax over KV tiles with VMEM scratch accumulators — the
standard TPU formulation: grid (batch·q_heads, q_blocks, kv_blocks)
with the kv dimension 'arbitrary' (sequential) so the running max/sum/
accumulator live in VMEM scratch across kv steps.  GQA is free: the
kv BlockSpec index-maps a group of q heads onto their shared kv head,
so KV is never materialized per-q-head.

Causal masking skips fully-masked kv blocks via @pl.when (no FLOPs, no
HBM reads are wasted on them — the Pallas pipeline still fetches the
block, which the hillclimb log discusses) and applies a triangular mask
on the diagonal blocks.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import resolve_interpret, NEG_INF, cdiv

__all__ = ["flash_attention_pallas"]


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *, scale, causal, bq, bk,
            n_kv_blocks, kv_len, causal_offset):
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    # For causal attention, blocks strictly above the diagonal contribute
    # nothing: q_pos_max = (qi+1)*bq - 1 < ki*bk = k_pos_min.
    run = True
    if causal:
        # query row i attends to keys <= i + causal_offset (offset = kv_len - sq)
        run = (qi + 1) * bq - 1 + causal_offset >= ki * bk

    @pl.when(run)
    def _compute():
        q = q_ref[0].astype(jnp.float32)              # (bq, d)
        k = k_ref[0].astype(jnp.float32)              # (bk, d)
        v = v_ref[0].astype(jnp.float32)              # (bk, d)

        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        ) * scale                                      # (bq, bk)

        k_pos = ki * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        valid = k_pos < kv_len                          # mask padded keys
        if causal:
            q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            valid = valid & (q_pos + causal_offset >= k_pos)
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_scr[...]                            # (bq, 1)
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # guard fully-masked rows (all -inf)
        m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)

        p = jnp.exp(s - m_safe)
        p = jnp.where(jnp.isfinite(s), p, 0.0)
        alpha = jnp.where(jnp.isfinite(m_prev), jnp.exp(m_prev - m_safe), 0.0)

        l_scr[...] = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[...] = alpha * acc_scr[...] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
        )
        m_scr[...] = m_new

    @pl.when(ki == n_kv_blocks - 1)
    def _finalize():
        l = l_scr[...]
        o = acc_scr[...] / jnp.where(l == 0.0, 1.0, l)
        o_ref[0] = o.astype(o_ref.dtype)


def flash_attention_pallas(
    q: jnp.ndarray,        # (B, Hq, Sq, D)
    k: jnp.ndarray,        # (B, Hkv, Skv, D)
    v: jnp.ndarray,        # (B, Hkv, Skv, D)
    *,
    causal: bool = True,
    scale: float | None = None,
    block_q: int = 128,
    block_k: int = 128,
    kv_len: int | None = None,   # true (unpadded) kv length
    q_len: int | None = None,    # true (unpadded) q length (for the causal offset)
    interpret: bool | None = None,
) -> jnp.ndarray:
    interpret = resolve_interpret(interpret)
    b, hq, sq, d = q.shape
    _, hkv, skv, _ = k.shape
    assert hq % hkv == 0, "GQA requires q_heads % kv_heads == 0"
    group = hq // hkv
    scale = (d ** -0.5) if scale is None else scale

    bq = min(block_q, sq)
    bk = min(block_k, skv)
    assert sq % bq == 0 and skv % bk == 0, "pad seq lens to block multiples"
    nq, nk = sq // bq, skv // bk

    qf = q.reshape(b * hq, sq, d)
    kf = k.reshape(b * hkv, skv, d)
    vf = v.reshape(b * hkv, skv, d)

    def kv_index(h, qi, ki):
        # fold q head -> kv head: global q-head index h = b*hq + i
        return (h // (group * hkv) * hkv + (h % hq) // group, ki, 0)

    kv_len_eff = kv_len if kv_len is not None else skv
    q_len_eff = q_len if q_len is not None else sq
    kernel = functools.partial(
        _kernel, scale=scale, causal=causal, bq=bq, bk=bk, n_kv_blocks=nk,
        kv_len=kv_len_eff, causal_offset=kv_len_eff - q_len_eff,
    )
    out = pl.pallas_call(
        kernel,
        grid=(b * hq, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda h, qi, ki: (h, qi, 0)),
            pl.BlockSpec((1, bk, d), kv_index),
            pl.BlockSpec((1, bk, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda h, qi, ki: (h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * hq, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="flash_attention_fwd",
    )(qf, kf, vf)
    return out.reshape(b, hq, sq, d)
