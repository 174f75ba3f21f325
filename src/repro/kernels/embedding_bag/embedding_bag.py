"""Pallas TPU kernel: EmbeddingBag (ragged gather + segment-reduce).

JAX has no native EmbeddingBag; the recsys hot path (huge sparse tables
→ per-bag sum/mean) is built here as a first-class op.  TPU adaptation:
dynamic row gathers are expressed with a *scalar-prefetch* grid spec —
the bag indices are prefetched into SMEM and drive the table BlockSpec's
index_map, so each grid step DMAs exactly the (1, E) table row it needs
from HBM into VMEM (the TPU-idiomatic sparse gather; there is no
warp-level shuffle to port).  The output block is revisited across the
L steps of a bag and accumulated in place.

Grid: (n_bags, bag_len).  Padding slots use index 0 with weight 0.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import resolve_interpret

__all__ = ["embedding_bag_pallas"]


def _kernel(idx_ref, table_ref, w_ref, o_ref, *, bag_len, mode):
    b = pl.program_id(0)
    l = pl.program_id(1)

    @pl.when(l == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    row = table_ref[...].astype(jnp.float32)          # (1, E)
    w = w_ref[...].astype(jnp.float32)                # (1, 1)
    o_ref[...] += (row * w).astype(o_ref.dtype)

    if mode == "mean":
        count = jnp.maximum(idx_ref[b, bag_len], 1).astype(jnp.float32)

        @pl.when(l == bag_len - 1)
        def _norm():
            o_ref[...] = (o_ref[...].astype(jnp.float32) / count).astype(o_ref.dtype)


def embedding_bag_pallas(
    table: jnp.ndarray,      # (V, E) float
    indices: jnp.ndarray,    # (B, L) int32, -1 padding
    weights: jnp.ndarray | None = None,   # (B, L) float32 per-sample weights
    *,
    mode: str = "sum",       # sum | mean
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Returns (B, E) pooled embeddings."""
    interpret = resolve_interpret(interpret)
    assert mode in ("sum", "mean")
    b, l = indices.shape
    v, e = table.shape

    valid = indices >= 0
    safe_idx = jnp.where(valid, indices, 0).astype(jnp.int32)
    if weights is None:
        w = valid.astype(jnp.float32)
    else:
        w = jnp.where(valid, weights, 0.0).astype(jnp.float32)
    # Scalar-prefetch operand: per-bag indices plus a trailing column with
    # the bag's valid count (used by mean normalization).
    counts = jnp.sum(valid, axis=1, dtype=jnp.int32)
    idx_sp = jnp.concatenate([safe_idx, counts[:, None]], axis=1)

    kernel = functools.partial(_kernel, bag_len=l, mode=mode)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, l),
        in_specs=[
            pl.BlockSpec((1, e), lambda bi, li, idx_ref: (idx_ref[bi, li], 0)),
            pl.BlockSpec((1, 1), lambda bi, li, idx_ref: (bi, li)),
        ],
        out_specs=pl.BlockSpec((1, e), lambda bi, li, idx_ref: (bi, 0)),
    )
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, e), table.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
        ),
        interpret=interpret,
        name="embedding_bag",
    )(idx_sp, table, w)
