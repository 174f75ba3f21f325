"""Plane-pruned block_scan: stream ONLY the match rule's active
(term, field) posting planes HBM→VMEM.

The baseline kernel (and the XLA executor path) DMAs the full
(T·F, W) occupancy tile per block and masks in VMEM — for a shallow
rule like mr_B (2 active planes of 16) that wastes 8× HBM bandwidth,
and the paper's whole point is that shallow rules are CHEAP.  Here the
occupancy stays in HBM and the kernel issues one DMA per ACTIVE plane,
driven by the plane list in a scalar-prefetched meta: bytes streamed =
``n_active × W`` words per block = u (the paper's cost accumulator),
not T·F·W.

Why manual DMAs: a single plane is a (1, W) row of the (T·F, W) tile,
and a BlockSpec of that shape breaks the TPU's (8, 128) tiling rule
(second-minor block dim 1 on an array dim of T·F), squeezed or not.
Fetching the whole tile and selecting rows in VMEM would stream T·F·W
words whatever the rule.  ``make_async_copy`` of one row is legal, so
each active plane is copied into its own row of a (T·F, W) VMEM buffer
and inactive rows are masked out by a per-plane flag.

Two entry points:

``block_scan_pruned_chunk``
    The serving/rollout kernel behind the ``pallas_block_scan`` scan
    backend (core/scan_backends.py): rule masks are TRACED (chosen by
    the policy at runtime).  Each launch evaluates a static chunk of C
    consecutive blocks for a whole query batch: grid (B, C), block
    start per lane read from the meta.  Out-of-range blocks are clamped
    to block n_blocks-1 and masked by the caller.

``block_scan_pruned_pallas``
    A static (host numpy) rule over every block of one occupancy
    tensor — the same kernel, with the index's blocks split into
    chunk-sized lanes that share one occupancy.

Semantics are pinned against ``block_scan_reference``
(kernels/block_scan/ref.py → core.match_rules.scan_block) for every
edge, including rules with ZERO active planes (nothing is read:
match = 0, v_inc = 0) and rules with no required terms (match = 0 but
v_inc still counts term hits among the planes the rule paid to
inspect — u is charged, so v is too).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.block_scan.block_scan import count_lanes, rule_match
from repro.kernels.common import cdiv, resolve_interpret

__all__ = [
    "block_scan_pruned_pallas", "block_scan_pruned_chunk", "build_rule_meta",
    "META_ROWS", "META_BP_COL",
]

META_ROWS = 3          # active plane list / per-plane flag / required per term
META_BP_COL = -1       # meta[:, 0, -1]: block start; meta[:, 1, -1]: n_active
COUNT_LANES = 128      # counts output row: lane 0 v_inc, lane 1 n_match


def build_rule_meta(
    allowed: jnp.ndarray,       # (B, T, F) bool — TRACED rule mask
    required: jnp.ndarray,      # (B, T) bool
    term_present: jnp.ndarray,  # (B, T) bool
    block_start: jnp.ndarray,   # (B,) int32 — first block of the chunk
) -> jnp.ndarray:
    """Scalar-prefetch meta for ``block_scan_pruned_chunk`` (traced).

    Row 0 lists the active planes (allowed ∧ present, flattened t·F+f)
    first, via a stable argsort; its last column is the lane's block
    start.  Row 1 flags each plane id active (0/1); its last column is
    the active-plane count.  Row 2 flags each term required ∧ present.
    """
    b, t, f = allowed.shape
    p_steps = t * f
    act = (allowed & term_present[:, :, None]).reshape(b, p_steps)
    order = jnp.argsort(~act, axis=1, stable=True).astype(jnp.int32)
    n_active = jnp.sum(act, axis=1, dtype=jnp.int32)

    ncols = max(p_steps + 1, 8)
    meta = jnp.zeros((b, META_ROWS, ncols), jnp.int32)
    meta = meta.at[:, 0, :p_steps].set(order)
    meta = meta.at[:, 0, META_BP_COL].set(block_start.astype(jnp.int32))
    meta = meta.at[:, 1, :p_steps].set(act.astype(jnp.int32))
    meta = meta.at[:, 1, META_BP_COL].set(n_active)
    meta = meta.at[:, 2, :t].set((required & term_present).astype(jnp.int32))
    return meta


def _chunk_kernel(meta_ref, occ_hbm, match_ref, counts_ref, buf, sems,
                  *, t: int, n_fields: int, n_blocks: int, shared_occ: bool):
    bi = pl.program_id(0)
    c = pl.program_id(1)
    chunk, w = match_ref.shape
    last = meta_ref.shape[-1] - 1
    blk = jnp.minimum(meta_ref[bi, 0, last] + c, n_blocks - 1)
    n_active = meta_ref[bi, 1, last]
    lane = 0 if shared_occ else bi

    def copy(k):
        plane = meta_ref[bi, 0, k]
        return pltpu.make_async_copy(
            occ_hbm.at[lane, blk, pl.ds(plane, 1)],
            buf.at[pl.ds(plane, 1)], sems.at[k])

    # Issue every active plane's DMA, then wait for all of them: only
    # n_active rows of the block ever leave HBM.
    lax.fori_loop(0, n_active, lambda k, _: copy(k).start(), None)
    lax.fori_loop(0, n_active, lambda k, _: copy(k).wait(), None)

    # Rows of inactive planes hold stale data: their flags mask them out.
    match, v_inc, n_match = rule_match(
        lambda p: buf[p:p + 1, :], lambda p: meta_ref[bi, 1, p] != 0,
        lambda j: meta_ref[bi, 2, j] != 0, t=t, f=n_fields)

    # The (C, ·) output tiles stay resident across the chunk axis; row c
    # is this block's.  Full-tile selects keep every store tile-aligned.
    rows = lax.broadcasted_iota(jnp.int32, (chunk, w), 0) == c
    match_ref[...] = jnp.where(rows, jnp.broadcast_to(match, (chunk, w)),
                               match_ref[...])
    rows = lax.broadcasted_iota(jnp.int32, (chunk, COUNT_LANES), 0) == c
    counts = count_lanes((chunk, COUNT_LANES), v_inc, n_match)
    counts_ref[...] = jnp.where(rows, counts, counts_ref[...])


def _chunk_call(occ, meta, *, chunk: int, n_terms: int, shared_occ: bool,
                interpret: bool | None):
    interpret = resolve_interpret(interpret)
    b = meta.shape[0]
    _, nb, tf, w = occ.shape
    kernel = functools.partial(_chunk_kernel, t=n_terms, n_fields=tf // n_terms,
                               n_blocks=nb, shared_occ=shared_occ)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, chunk),
        in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=[
            pl.BlockSpec((None, chunk, w), lambda bi, c, m: (bi, 0, 0)),
            pl.BlockSpec((None, chunk, COUNT_LANES), lambda bi, c, m: (bi, 0, 0)),
        ],
        scratch_shapes=[pltpu.VMEM((tf, w), jnp.uint32),
                        pltpu.SemaphoreType.DMA((tf,))],
    )
    match, counts = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((b, chunk, w), jnp.uint32),
            jax.ShapeDtypeStruct((b, chunk, COUNT_LANES), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
        name="block_scan_pruned_chunk",
    )(meta, occ)
    return match, counts[..., 0], counts[..., 1]


def block_scan_pruned_chunk(
    occ: jnp.ndarray,            # (B, n_blocks, T*F, W) uint32
    meta: jnp.ndarray,           # (B, META_ROWS, ncols) int32 — build_rule_meta
    *,
    chunk: int,
    n_terms: int,
    interpret: bool | None = None,
):
    """Evaluate each lane's (traced) rule over ``chunk`` consecutive
    blocks starting at the lane's block start.

    Returns (match (B, chunk, W) uint32, v_inc (B, chunk) int32,
    n_match (B, chunk) int32).  Blocks past n_blocks-1 are clamped to
    the last block — callers mask them (core/scan_backends.py masks by
    the stopping condition, which includes block_ptr < n_blocks).
    """
    return _chunk_call(occ, meta, chunk=chunk, n_terms=n_terms,
                       shared_occ=False, interpret=interpret)


def block_scan_pruned_pallas(
    occ: jnp.ndarray,            # (n_blocks, T, F, W) uint32
    allowed: np.ndarray,         # (T, F) bool — STATIC (host) rule mask
    required: np.ndarray,        # (T,) bool — static
    term_present: np.ndarray,    # (T,) bool — static
    *,
    chunk: int = 32,
    interpret: bool | None = None,
):
    """Returns (match (nb, W) u32, v_inc (nb,) i32, n_match (nb,) i32).
    Only the rule's active planes are ever read from HBM."""
    nb, t, f, w = occ.shape
    chunk = min(chunk, nb)
    lanes = cdiv(nb, chunk)
    meta = build_rule_meta(
        jnp.broadcast_to(jnp.asarray(allowed, bool), (lanes, t, f)),
        jnp.broadcast_to(jnp.asarray(required, bool), (lanes, t)),
        jnp.broadcast_to(jnp.asarray(term_present, bool), (lanes, t)),
        jnp.arange(lanes, dtype=jnp.int32) * chunk)
    match, v_inc, n_match = _chunk_call(
        occ.reshape(1, nb, t * f, w), meta, chunk=chunk, n_terms=t,
        shared_occ=True, interpret=interpret)
    return (match.reshape(lanes * chunk, w)[:nb],
            v_inc.reshape(-1)[:nb], n_match.reshape(-1)[:nb])
