"""Pluggable index-scan backends: HOW a match rule streams the index.

The paper prices a rule execution in ``u`` — posting-plane block reads
— but pricing is only honest if bytes streamed track u.  The rule
EXECUTION semantics (paper §3: scan blocks until Δu ≥ du_quota,
Δv ≥ dv_quota, end of index, or episode budget) are fixed; the SCAN
STRATEGY underneath is not, so it is a backend:

``"xla"``
    The reference: a ``lax.while_loop`` over single blocks, each block
    evaluated on the full (T·F, W) occupancy tile
    (``core.match_rules.scan_block``).  Bit-exact semantics, but bytes
    streamed ∝ T·F·W per block regardless of rule depth.

``"pallas_block_scan"``
    Chunked plane-pruned Pallas execution
    (kernels/block_scan/block_scan_pruned.py): each kernel launch
    SPECULATIVELY evaluates a static chunk of C consecutive blocks for
    the whole batch, streaming only the rule's active (term, field)
    planes — bytes ∝ u.  The quota-crossing block is then located by
    cumulative sums of the per-block (u_inc, v_inc) increments, and
    every update (matched / cand / topn / counters) past it is masked,
    so the final :class:`EnvState` is bit-for-bit identical to the
    ``"xla"`` loop — stopping semantics preserved at chunk granularity,
    with at most C-1 blocks of speculative overshoot in bandwidth.

A backend's ``run_rule`` is BATCHED: every array argument carries a
leading query-batch axis.  ``"xla"`` vmaps the single-query loop;
``"pallas_block_scan"`` folds the batch into the kernel grid and runs
one batch-level ``while_loop`` over chunks (lanes whose stopping
condition already fired are masked to a no-op, so per-lane results
never depend on other lanes).

Registering a new strategy::

    from repro.core.scan_backends import ScanBackend, register_scan_backend

    class MyBackend(ScanBackend):
        name = "my_backend"
        def run_rule(self, cfg, occ, scores, term_present, state,
                     allowed, required, du_quota, dv_quota):
            ...

    register_scan_backend(MyBackend())

The name then works everywhere a backend is selectable:
``unified_rollout(..., backend=...)``, ``EngineConfig.backend``,
``SystemConfig.backend``, and the ``--backend`` launch flags.
"""
from __future__ import annotations

from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.index.blocks import WORD_BITS
from repro.kernels.block_scan.block_scan_pruned import (
    block_scan_pruned_chunk, build_rule_meta,
)

from .environment import EnvConfig, EnvState
from .match_rules import block_cost, scan_block

__all__ = [
    "ScanBackend", "XlaScanBackend", "PallasBlockScanBackend",
    "register_scan_backend", "get_scan_backend", "available_backends",
    "xla_run_rule", "adaptive_chunk_blocks", "DEFAULT_CHUNK_BLOCKS",
    "MAX_ADAPTIVE_CHUNK",
]

DEFAULT_CHUNK_BLOCKS = 4
MAX_ADAPTIVE_CHUNK = 32


class ScanBackend:
    """Protocol: one rule execution over a BATCH of queries.

    ``run_rule(cfg, occ, scores, term_present, state, allowed,
    required, du_quota, dv_quota) -> EnvState`` where every array
    argument has a leading (B,) axis: occ (B, n_blocks, T, F, W)
    uint32, scores (B, n_docs_padded) float32, term_present (B, T)
    bool, state a batched :class:`EnvState`, allowed (B, T, F) bool,
    required (B, T) bool, du_quota / dv_quota (B,) int32.

    Implementations must reproduce the paper's §3 stopping condition
    exactly — scan block j iff, with the state BEFORE block j,
    ``u - u0 < du_quota`` ∧ ``v - v0 < dv_quota`` ∧
    ``block_ptr < n_blocks`` ∧ ``u < u_budget`` ∧ ``¬done`` — and must
    not couple lanes (lane i's output may not depend on lane j's rule
    or state).
    """

    name: str = ""

    def run_rule(self, cfg: EnvConfig, occ, scores, term_present, state,
                 allowed, required, du_quota, dv_quota) -> EnvState:
        raise NotImplementedError

    def describe(self) -> dict:
        return {"name": self.name, "kind": type(self).__name__}


_SCAN_BACKENDS: Dict[str, ScanBackend] = {}


def register_scan_backend(backend: ScanBackend) -> ScanBackend:
    """Register (or replace) a backend under ``backend.name``."""
    if not backend.name:
        raise ValueError(f"{type(backend).__name__} has no name")
    _SCAN_BACKENDS[backend.name] = backend
    return backend


def get_scan_backend(name: str) -> ScanBackend:
    try:
        return _SCAN_BACKENDS[name]
    except KeyError:
        raise KeyError(
            f"unknown scan backend {name!r}; available: "
            f"{available_backends()}") from None


def available_backends() -> Tuple[str, ...]:
    return tuple(sorted(_SCAN_BACKENDS))


# ------------------------------------------------------- "xla" (reference)
def _unpack_words(words: jnp.ndarray) -> jnp.ndarray:
    """(W,) uint32 -> (W*32,) bool, LSB-first (matches blocks.pack_bits)."""
    shifts = jnp.arange(WORD_BITS, dtype=jnp.uint32)
    bits = (words[:, None] >> shifts[None, :]) & jnp.uint32(1)
    return bits.reshape(-1).astype(bool)


def _scan_one_block(
    cfg: EnvConfig,
    occ: jnp.ndarray,          # (n_blocks, T, F, W) uint32
    scores: jnp.ndarray,       # (n_docs_padded,) float32 — precomputed L1 scores
    term_present: jnp.ndarray, # (T,) bool
    allowed: jnp.ndarray,      # (T, F) bool
    required: jnp.ndarray,     # (T,) bool
    state: EnvState,
) -> EnvState:
    W, D = cfg.words_per_block, cfg.block_docs
    bp = state.block_ptr
    occ_block = lax.dynamic_index_in_dim(occ, bp, axis=0, keepdims=False)

    match_words, v_inc = scan_block(occ_block, allowed, required, term_present)

    # Dedup against docs already selected by earlier rules / passes.
    old = lax.dynamic_slice(state.matched, (bp * W,), (W,))
    new_words = match_words & ~old
    matched = lax.dynamic_update_slice(state.matched, old | match_words, (bp * W,))

    new_bits = _unpack_words(new_words)                       # (D,) bool
    doc_ids = bp * D + jnp.arange(D, dtype=jnp.int32)

    # Append new docs to the fixed-K buffer in scan (static-rank) order.
    pos = state.cand_cnt + jnp.cumsum(new_bits.astype(jnp.int32)) - 1
    write_pos = jnp.where(new_bits & (pos < cfg.max_candidates), pos, cfg.max_candidates)
    cand = state.cand.at[write_pos].set(doc_ids, mode="drop")
    n_new = jnp.sum(new_bits, dtype=jnp.int32)
    cand_cnt = jnp.minimum(state.cand_cnt + n_new, cfg.max_candidates)

    # Update running top-n L1 scores with the block's new docs.
    block_scores = lax.dynamic_slice(scores, (bp * D,), (D,))
    masked = jnp.where(new_bits, block_scores, -jnp.inf)
    topn, _ = lax.top_k(jnp.concatenate([state.topn, masked]), cfg.n_top)

    u_inc = block_cost(allowed, term_present)
    return EnvState(
        block_ptr=bp + 1,
        u=state.u + u_inc,
        v=state.v + v_inc,
        matched=matched,
        cand=cand,
        cand_cnt=cand_cnt,
        topn=topn,
        done=state.done,
    )


def xla_run_rule(
    cfg: EnvConfig,
    occ: jnp.ndarray,
    scores: jnp.ndarray,
    term_present: jnp.ndarray,
    state: EnvState,
    allowed: jnp.ndarray,
    required: jnp.ndarray,
    du_quota: jnp.ndarray,
    dv_quota: jnp.ndarray,
) -> EnvState:
    """SINGLE-QUERY reference loop (the pre-refactor ``execute_rule``
    body): scan one block at a time until the stopping condition."""
    u0, v0 = state.u, state.v

    def cond(s: EnvState):
        return (
            (s.u - u0 < du_quota)
            & (s.v - v0 < dv_quota)
            & (s.block_ptr < cfg.n_blocks)
            & (s.u < cfg.u_budget)
            & ~s.done
        )

    def body(s: EnvState):
        return _scan_one_block(cfg, occ, scores, term_present, allowed, required, s)

    return lax.while_loop(cond, body, state)


class XlaScanBackend(ScanBackend):
    """Block-at-a-time XLA scanning: vmap of the reference while_loop."""

    name = "xla"

    def run_rule(self, cfg, occ, scores, term_present, state,
                 allowed, required, du_quota, dv_quota) -> EnvState:
        return jax.vmap(partial(xla_run_rule, cfg))(
            occ, scores, term_present, state, allowed, required,
            du_quota, dv_quota)


# ------------------------------------------- "pallas_block_scan" (chunked)
def adaptive_chunk_blocks(n_blocks: int, du_quota, u_inc,
                          u_budget: int) -> int:
    """Pick a speculation depth C from the rule's quota and plane count.

    A rule's expected scan length is ``du_quota / planes_read`` blocks:
    deep rules (many active planes) cross their Δu quota in a few
    blocks, so a large C wastes up to C-1 blocks of bandwidth past the
    crossing; shallow sweeps (few planes) run far, so a small C pays
    launch overhead per handful of blocks.  C is sized for the
    longest-running lane of the batch (the lanes that would otherwise
    need the most chunk launches), clamped to [1, min(n_blocks,
    MAX_ADAPTIVE_CHUNK)].

    The estimate needs CONCRETE quota/plane values: under a jit trace
    (where the policy picks rules dynamically and quotas are tracers)
    it falls back to :data:`DEFAULT_CHUNK_BLOCKS` — shapes baked into
    the kernel grid cannot depend on traced values."""
    try:
        du = np.asarray(du_quota, dtype=np.float64)
        planes = np.asarray(u_inc, dtype=np.float64)
    except jax.errors.TracerArrayConversionError:
        return DEFAULT_CHUNK_BLOCKS
    # A lane also stops at the episode budget / end of index, whichever
    # comes first; zero-plane rules cost nothing and sweep to the end.
    blocks = np.where(planes > 0,
                      np.minimum(du, u_budget) / np.maximum(planes, 1.0),
                      n_blocks)
    c = int(np.ceil(np.max(blocks, initial=1.0)))
    return int(np.clip(c, 1, min(n_blocks, MAX_ADAPTIVE_CHUNK)))


def _apply_chunk(
    cfg: EnvConfig,
    chunk: int,
    state: EnvState,           # single lane
    match: jnp.ndarray,        # (chunk, W) uint32 — per-block match words
    v_inc: jnp.ndarray,        # (chunk,) int32
    scan_mask: jnp.ndarray,    # (chunk,) bool — block actually scanned
    u_inc: jnp.ndarray,        # () int32 — planes read per block
    scores: jnp.ndarray,       # (n_docs_padded,) float32
) -> EnvState:
    """Fold one speculative chunk into the state, masking every update
    past the quota-crossing block.  Block-for-block identical to
    iterating ``_scan_one_block`` over the scanned prefix: chunk blocks
    are disjoint word ranges, so dedup only looks at ``state.matched``;
    the candidate cumsum spans the chunk in scan order; and top-n over
    the union equals iterated top-n."""
    W, D, K = cfg.words_per_block, cfg.block_docs, cfg.max_candidates
    bp = state.block_ptr
    n = jnp.sum(scan_mask, dtype=jnp.int32)

    word_mask = jnp.where(jnp.repeat(scan_mask, W),
                          jnp.uint32(0xFFFFFFFF), jnp.uint32(0))
    mwords = match.reshape(chunk * W) & word_mask

    # Pad so the chunk slice stays aligned when bp is near the end of
    # the index (dynamic_slice would otherwise clamp and shift blocks).
    total = state.matched.shape[0]
    padded = jnp.concatenate(
        [state.matched, jnp.zeros((chunk * W,), jnp.uint32)])
    old = lax.dynamic_slice(padded, (bp * W,), (chunk * W,))
    new_words = mwords & ~old
    matched = lax.dynamic_update_slice(
        padded, old | mwords, (bp * W,))[:total]

    new_bits = _unpack_words(new_words)                  # (chunk*D,) bool
    doc_ids = bp * D + jnp.arange(chunk * D, dtype=jnp.int32)
    pos = state.cand_cnt + jnp.cumsum(new_bits.astype(jnp.int32)) - 1
    write_pos = jnp.where(new_bits & (pos < K), pos, K)
    cand = state.cand.at[write_pos].set(doc_ids, mode="drop")
    n_new = jnp.sum(new_bits, dtype=jnp.int32)
    cand_cnt = jnp.minimum(state.cand_cnt + n_new, K)

    spad = jnp.concatenate([scores, jnp.zeros((chunk * D,), scores.dtype)])
    block_scores = lax.dynamic_slice(spad, (bp * D,), (chunk * D,))
    masked = jnp.where(new_bits, block_scores, -jnp.inf)
    topn, _ = lax.top_k(jnp.concatenate([state.topn, masked]), cfg.n_top)

    return EnvState(
        block_ptr=bp + n,
        u=state.u + n * u_inc,
        v=state.v + jnp.sum(v_inc * scan_mask, dtype=jnp.int32),
        matched=matched,
        cand=cand,
        cand_cnt=cand_cnt,
        topn=topn,
        done=state.done,
    )


class PallasBlockScanBackend(ScanBackend):
    """Chunked plane-pruned Pallas rule execution (bytes streamed ∝ u).

    ``chunk`` is the speculation depth C: blocks evaluated per kernel
    launch.  Larger C amortizes launch overhead and deepens the DMA
    pipeline but wastes up to C-1 blocks of bandwidth past the quota
    crossing.  ``chunk=None`` picks C adaptively per rule from its
    quota/plane count (:func:`adaptive_chunk_blocks`): deep rules get a
    small C, shallow sweeps a large one — falling back to
    :data:`DEFAULT_CHUNK_BLOCKS` when quotas are traced.  The final
    state is C-invariant either way (pinned by
    ``tests/test_scan_backends.py::test_chunk_size_invariance``).
    ``interpret=None`` is decided per kernel call by
    ``kernels.common.resolve_interpret`` (interpret mode off the TPU,
    compiled on it).
    """

    name = "pallas_block_scan"

    def __init__(self, chunk: int | None = DEFAULT_CHUNK_BLOCKS,
                 interpret: bool | None = None):
        self.chunk = chunk
        self.interpret = interpret
        self.last_chunk: int | None = None   # introspection/tests

    def describe(self) -> dict:
        return dict(super().describe(),
                    chunk="adaptive" if self.chunk is None else self.chunk)

    def run_rule(self, cfg, occ, scores, term_present, state,
                 allowed, required, du_quota, dv_quota) -> EnvState:
        b, nb, t, f, w = occ.shape
        # Batched block_cost: planes the rule reads per block, per lane
        # — also the adaptive chunk heuristic's denominator.
        u_inc = jnp.sum(allowed & term_present[:, :, None], axis=(1, 2),
                        dtype=jnp.int32)                           # (B,)
        if self.chunk is None:
            chunk = adaptive_chunk_blocks(nb, du_quota, u_inc,
                                          cfg.u_budget)
        else:
            chunk = self.chunk
        chunk = max(1, min(chunk, nb))
        self.last_chunk = chunk
        occ2 = occ.reshape(b, nb, t * f, w)
        u0, v0 = state.u, state.v
        # The rule is loop-invariant: build the plane-ordering meta once
        # and only refresh the block-start column per chunk iteration.
        meta0 = build_rule_meta(allowed, required, term_present,
                                jnp.zeros((b,), jnp.int32))

        def lane_cond(s: EnvState):
            return (
                (s.u - u0 < du_quota)
                & (s.v - v0 < dv_quota)
                & (s.block_ptr < nb)
                & (s.u < cfg.u_budget)
                & ~s.done
            )

        def cond(s: EnvState):
            return jnp.any(lane_cond(s))

        def body(s: EnvState):
            meta = meta0.at[:, 0, -1].set(s.block_ptr.astype(jnp.int32))
            match, v_inc, _ = block_scan_pruned_chunk(
                occ2, meta, chunk=chunk, n_terms=t,
                interpret=self.interpret)

            # Locate the stopping block per lane by cumulative sums:
            # block j is scanned iff the §3 condition holds at the
            # state BEFORE block j.  Every term is monotone in j, so
            # the scanned set is a prefix.
            j = jnp.arange(chunk, dtype=jnp.int32)[None, :]
            u_before = s.u[:, None] + j * u_inc[:, None]
            v_prefix = jnp.concatenate(
                [jnp.zeros((b, 1), jnp.int32),
                 jnp.cumsum(v_inc[:, :-1], axis=1)], axis=1)
            v_before = s.v[:, None] + v_prefix
            ok = (
                (u_before - u0[:, None] < du_quota[:, None])
                & (v_before - v0[:, None] < dv_quota[:, None])
                & (s.block_ptr[:, None] + j < nb)
                & (u_before < cfg.u_budget)
                & ~s.done[:, None]
            )
            scan_mask = jnp.cumprod(ok.astype(jnp.int32), axis=1) > 0
            return jax.vmap(partial(_apply_chunk, cfg, chunk))(
                s, match, v_inc, scan_mask, u_inc, scores)

        return lax.while_loop(cond, body, state)


register_scan_backend(XlaScanBackend())
register_scan_backend(PallasBlockScanBackend())
