"""Rollout executor: pre-compiled per-(bucket, policy-structure)
executables, a pluggable rollout backend, and multi-shard
scatter–gather.

The full L0→L1 serve step — policy rollout per index shard through
``unified_rollout``, candidate scatter to global doc ids, static-rank
merge across shards (`merge_shard_candidates`), and L1 rank/prune — is
fused into one function and AOT-compiled (``jit(...).lower(...)
.compile()``) per (bucket size, policy structure).  Policy *parameters*
(Q-tables, plan entries, ε) and the state bins are runtime arguments,
so one executable serves every query category sharing a policy
structure, and publishing a new snapshot through a ``PolicyStore``
never retraces; in steady state the compile count is
``len(BucketConfig.buckets()) × n_policy_structures``.

The rollout inner loop is a *backend* chosen at construction and baked
into the AOT compile key: any name registered in the core scan-backend
registry (``repro.core.scan_backends`` — ``"xla"`` block-at-a-time
scanning, ``"pallas_block_scan"`` chunked plane-pruned Pallas, both
bit-identical) runs through ``unified_rollout(..., backend=...)``;
serving-only rollout strategies can additionally be registered here
with ``register_rollout_backend``.

Sharding here is the logical split of the paper's multi-machine index:
the block axis is cut into ``n_shards`` equal slices, each running its
own rollout under a per-shard u budget ("the same policy is applied on
every machine, which may lead to executing different sequences of match
rules"), then per-shard candidates are gathered and merged by static
rank before L1 — mirroring launch/steps.py's shard_map serve cell but
driven from a single host process.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, Iterable, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from functools import partial

from repro.core.rollout import unified_rollout
from repro.core.scan_backends import available_backends as scan_backends
from repro.core.telescope import l1_prune, merge_shard_candidates
from repro.index.corpus import N_FIELDS
from repro.obs import NULL_SPAN, NULL_TRACER
from repro.policies import Policy

__all__ = ["ShardedExecutor", "available_backends",
           "register_rollout_backend", "resolve_rollout_backend"]


# ------------------------------------------------------------------ backends
# A rollout backend runs one policy rollout over one index shard slice:
#   backend(cfg, ruleset, bins, policy, t_max, occ, scores, tp) -> EnvState
# Every core scan backend (repro.core.scan_backends) is automatically a
# rollout backend via unified_rollout(..., backend=name); this registry
# holds serving-only overrides/extensions.
ROLLOUT_BACKENDS: Dict[str, Callable] = {}


def register_rollout_backend(name: str):
    def deco(fn: Callable) -> Callable:
        ROLLOUT_BACKENDS[name] = fn
        return fn
    return deco


def available_backends() -> Tuple[str, ...]:
    """Serving-selectable rollout backends: the core scan-backend
    registry plus any serving-level registrations."""
    return tuple(sorted(set(ROLLOUT_BACKENDS) | set(scan_backends())))


def _scan_backend_rollout(name, cfg, ruleset, bins, policy, t_max, occ,
                          scores, tp):
    return unified_rollout(cfg, ruleset, bins, policy, t_max,
                           occ, scores, tp, backend=name).final_state


def resolve_rollout_backend(name: str) -> Callable:
    if name in ROLLOUT_BACKENDS:
        return ROLLOUT_BACKENDS[name]
    if name in scan_backends():
        return partial(_scan_backend_rollout, name)
    raise ValueError(
        f"unknown rollout backend {name!r}; available: "
        f"{available_backends()}")


class ShardedExecutor:
    def __init__(self, system, n_shards: int = 1, keep: int = 100,
                 backend: str = "xla"):
        if system.bins is None or system.qcfg is None:
            raise ValueError("system needs fit_state_bins() before serving")
        nb = system.env_cfg.n_blocks
        if n_shards < 1 or nb % n_shards:
            raise ValueError(f"n_shards={n_shards} must divide n_blocks={nb}")
        self.system = system
        self.n_shards = n_shards
        self.keep = keep
        self.backend = backend
        self._backend_fn = resolve_rollout_backend(backend)
        self.blocks_per_shard = nb // n_shards
        self.docs_per_shard = self.blocks_per_shard * system.env_cfg.block_docs
        # Each shard scans its slice under the full per-machine u budget.
        self.shard_env_cfg = dataclasses.replace(
            system.env_cfg, n_blocks=self.blocks_per_shard)
        self._jit = jax.jit(self._serve_fn)
        self._compiled: Dict[tuple, jax.stages.Compiled] = {}
        self.compile_count = 0
        # Set by the owning engine when tracing is on; compiles are the
        # dominant cold-start latency, so each gets its own span.
        self.tracer = NULL_TRACER

    # ----------------------------------------------------------- the step
    def _serve_fn(self, bins, policy, occ, scores, term_present):
        """(B, NB, T, F, W) occupancy → (ids, scores, u, cand_cnt).
        Named scopes ``rollout``, ``merge`` and ``l1_prune`` label its
        device ops in a profiler trace."""
        merged, u_tot, cand_cnt = self.merged_candidates(
            bins, policy, occ, scores, term_present)
        with jax.named_scope("l1_prune"):
            ids, sc = l1_prune(scores, merged, keep=self.keep)
        return ids, sc, u_tot, cand_cnt

    def merged_candidates(self, bins, policy, occ, scores, term_present):
        """Per-shard rollouts merged by static rank, before L1 pruning:
        (merged (B, max_candidates) global doc ids, u summed over shards,
        merged candidate count)."""
        sys_ = self.system
        s, ds = self.n_shards, self.docs_per_shard
        b = occ.shape[0]
        t_max = policy.horizon or sys_.qcfg.t_max
        occ_sh = occ.reshape(b, s, self.blocks_per_shard, *occ.shape[2:])
        occ_sh = jnp.moveaxis(occ_sh, 1, 0)               # (S, B, nb/S, T, F, W)
        scores_sh = jnp.moveaxis(scores.reshape(b, s, ds), 1, 0)  # (S, B, ds)

        def one_shard(o, sc):
            return self._backend_fn(self.shard_env_cfg, sys_.ruleset, bins,
                                    policy, t_max, o, sc, term_present)

        with jax.named_scope("rollout"):
            final = jax.vmap(one_shard)(occ_sh, scores_sh)

        with jax.named_scope("merge"):
            shard_base = (jnp.arange(s, dtype=jnp.int32) * ds)[:, None, None]
            global_cand = jnp.where(final.cand >= 0,
                                    final.cand + shard_base, -1)
            merged = merge_shard_candidates(
                global_cand, keep=sys_.env_cfg.max_candidates)   # (B, K)
            u_tot = jnp.sum(final.u, axis=0)
            cand_cnt = jnp.sum((merged >= 0).astype(jnp.int32), axis=1)
        return merged, u_tot, cand_cnt

    # ------------------------------------------------------------ compile
    @staticmethod
    def _policy_key(policy: Policy) -> tuple:
        leaves, treedef = jax.tree_util.tree_flatten(policy)
        return (treedef,
                tuple((tuple(l.shape), str(l.dtype)) for l in leaves))

    def _abstract_args(self, bucket: int, policy: Policy):
        sys_ = self.system
        cfg = sys_.env_cfg
        t = sys_.log.terms.shape[1]
        f = N_FIELDS
        w = cfg.words_per_block
        sd = jax.ShapeDtypeStruct
        occ = sd((bucket, cfg.n_blocks, t, f, w), jnp.uint32)
        scores = sd((bucket, cfg.n_blocks * cfg.block_docs), jnp.float32)
        tp = sd((bucket, t), jnp.bool_)
        bins = jax.tree_util.tree_map(
            lambda x: sd(x.shape, x.dtype), sys_.bins)
        pol_abs = jax.tree_util.tree_map(
            lambda x: sd(x.shape, x.dtype), policy)
        return bins, pol_abs, occ, scores, tp

    def compiled_for(self, bucket: int, policy: Policy,
                     level: int = 0) -> jax.stages.Compiled:
        if not isinstance(policy, Policy):
            raise TypeError(
                f"expected a repro.policies.Policy, got {type(policy).__name__}; "
                "raw Q-table arrays are no longer accepted — wrap with "
                "TabularQPolicy(q)")
        # The backend AND the service level are part of the compile key:
        # each scan strategy lowers to a distinct executable even at
        # equal bucket/policy, and a degraded (SHALLOW) execution never
        # shares an executable with FULL serving — even if a future
        # fallback happens to share the live policy's structure, the
        # ladder keeps its own compile row.
        key = (bucket, self.backend, int(level), self._policy_key(policy))
        exe = self._compiled.get(key)
        if exe is None:
            with self.tracer.span("compile", bucket=bucket,
                                  backend=self.backend, level=int(level)):
                exe = self._jit.lower(
                    *self._abstract_args(bucket, policy)).compile()
            self._compiled[key] = exe
            self.compile_count += 1
        return exe

    def warmup(self, buckets: Iterable[int], policies: Iterable[Policy],
               level: int = 0) -> None:
        policies = list(policies)
        for b in buckets:
            for pol in policies:
                self.compiled_for(b, pol, level)

    # ------------------------------------------------------------ execute
    def execute(self, policy: Policy, occ, scores, term_present,
                level: int = 0, span=NULL_SPAN
                ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        """Run one micro-batch through its pre-compiled executable:
        ``dispatch`` the call, ``device_wait`` until its outputs (and
        the inputs' device work before them) are done, ``d2h`` the
        copies back, each a child of ``span``."""
        exe = self.compiled_for(occ.shape[0], policy, level)
        with span.child("dispatch"):
            ids, sc, u, cnt = exe(self.system.bins, policy, occ, scores,
                                  term_present)
        with span.child("device_wait"):
            jax.block_until_ready(ids)
        with span.child("d2h"):
            return (np.asarray(ids), np.asarray(sc), np.asarray(u),
                    np.asarray(cnt))
