"""Pallas TPU kernel: match-rule evaluation over bitpacked index blocks.

The paper's hot loop — "documents are scanned based on the chosen match
plan" — adapted to TPU (DESIGN.md §3): posting occupancy is streamed
HBM→VMEM in bitpacked tiles and evaluated with VPU bitwise ops +
population counts.  Deliberately MXU-free and memory-bound: its cost is
exactly the paper's ``u`` (bytes of index read).

Layout:
    occ      (n_blocks, T*F, W) uint32    one W-word plane per (term, field)
    masks    (2, T*F)           int32     SMEM; row 0: allowed∧present per
                                          plane, row 1: required∧present
                                          per term (first T columns)
Outputs per index block:
    match    (n_blocks, W)      uint32    docs satisfying ∧_t ∨_f occ
    counts   (n_blocks, 8)      int32     col 0: v increment (term matches),
                                          col 1: matched-doc count

Grid tiles BB index blocks per step; each VMEM tile is
BB × T·F × W × 4 B (e.g. 8 × 16 × 128 × 4 = 64 KiB), well inside the
~16 MiB VMEM budget, with double-buffered HBM streaming handled by the
Pallas pipeline.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.common import cdiv, resolve_interpret

__all__ = ["block_scan_pallas", "rule_match", "count_lanes"]


def _popcount_rows(x: jnp.ndarray) -> jnp.ndarray:
    """(R, W) uint32 → (R, 1) int32 set-bit count per row."""
    return jnp.sum(jax.lax.population_count(x).astype(jnp.int32), axis=1,
                   keepdims=True)


def rule_match(plane, plane_active, term_required, *, t: int, f: int):
    """One match rule over a tile of R blocks, in elementwise ops only:
    the TPU has no vector reductions over unsigned integers, so the
    per-term OR over fields and the AND over required terms are
    unrolled over the static T and F.

    ``plane(p)`` gives plane p's (R, W) uint32 rows; ``plane_active(p)``
    and ``term_required(j)`` give scalar flags (allowed∧present,
    required∧present).  Returns (match (R, W) uint32, v_inc (R, 1) int32,
    n_match (R, 1) int32).
    """
    conj, any_req, v_inc = None, False, 0
    for j in range(t):
        tf_or = None
        for k in range(f):
            row = plane(j * f + k)
            row = jnp.where(plane_active(j * f + k), row, jnp.zeros_like(row))
            tf_or = row if tf_or is None else tf_or | row
        req = term_required(j)
        term = jnp.where(req, tf_or, jnp.full_like(tf_or, 0xFFFFFFFF))
        conj = term if conj is None else conj & term
        any_req = any_req | req
        v_inc = v_inc + _popcount_rows(tf_or)
    match = jnp.where(any_req, conj, jnp.zeros_like(conj))
    return match, v_inc, _popcount_rows(match)


def count_lanes(shape, v_inc, n_match):
    """Counts tile: lane 0 v_inc, lane 1 n_match, zeros elsewhere."""
    lanes = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return jnp.where(lanes == 0, v_inc, jnp.where(lanes == 1, n_match, 0))


def _kernel(masks_ref, occ_ref, match_ref, counts_ref, *, t: int, f: int):
    # masks (SMEM): row 0 allowed∧present per plane, row 1
    # required∧present per term; planes are read with static indices.
    match, v_inc, n_match = rule_match(
        lambda p: occ_ref[:, p, :], lambda p: masks_ref[0, p] != 0,
        lambda j: masks_ref[1, j] != 0, t=t, f=f)
    match_ref[...] = match
    counts_ref[...] = count_lanes(counts_ref.shape, v_inc, n_match)


def block_scan_pallas(
    occ: jnp.ndarray,        # (n_blocks, T, F, W) uint32
    allowed: jnp.ndarray,    # (T, F) bool
    required: jnp.ndarray,   # (T,) bool
    term_present: jnp.ndarray,  # (T,) bool
    *,
    block_bb: int = 8,
    interpret: bool | None = None,
):
    """Evaluate one match rule over every index block.

    Returns (match_words (n_blocks, W) uint32, v_inc (n_blocks,) int32,
    n_match (n_blocks,) int32).
    """
    interpret = resolve_interpret(interpret)
    nb, t, f, w = occ.shape
    occ2 = occ.reshape(nb, t * f, w)
    pad = cdiv(nb, block_bb) * block_bb - nb
    if pad:
        occ2 = jnp.pad(occ2, ((0, pad), (0, 0), (0, 0)))

    amask = (allowed & term_present[:, None]).astype(jnp.int32).reshape(t * f)
    rmask = (required & term_present).astype(jnp.int32)
    masks = jnp.zeros((2, t * f), jnp.int32).at[0].set(amask).at[1, :t].set(rmask)

    grid = (cdiv(nb, block_bb),)
    kernel = functools.partial(_kernel, t=t, f=f)
    match, counts = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((block_bb, t * f, w), lambda b: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((block_bb, w), lambda b: (b, 0)),
            pl.BlockSpec((block_bb, 8), lambda b: (b, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((grid[0] * block_bb, w), jnp.uint32),
            jax.ShapeDtypeStruct((grid[0] * block_bb, 8), jnp.int32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
        name="block_scan",
    )(masks, occ2)
    return match[:nb], counts[:nb, 0], counts[:nb, 1]
