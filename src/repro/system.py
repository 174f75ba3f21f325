"""Top-level orchestrator: the full retrieval system of the paper.

Wires corpus → inverted index → query log → L1 ranker → state bins →
production plans → Q-learning, and exposes train/evaluate entry points
used by examples, tests and benchmarks.  This is the single-host (one
index shard) path; `repro.launch.serve` distributes it over the mesh.
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.environment import EnvConfig
from repro.core.match_plan import MatchPlan, plan_rollout, production_plans
from repro.core.match_rules import RuleSet, default_rule_library
from repro.core.qlearning import QConfig, init_q, linear_epsilon, train_batch
from repro.core.reward import r_agent
from repro.core.rollout import unified_rollout
from repro.core.state_bins import StateBins, fit_bins
from repro.obs import NULL_SPAN
from repro.policies import PolicyStore, StaticPlanPolicy, TabularQPolicy
from repro.data.querylog import CAT1, CAT2, QueryLog, QueryLogConfig, generate_querylog
from repro.index.builder import InvertedIndex, batch_query_occupancy, build_index
from repro.index.corpus import Corpus, CorpusConfig, generate_corpus
from repro.ranking.features import doc_features
from repro.ranking.l1_ranker import idf_for_terms, init_l1, score_all_docs, train_l1
from repro.ranking.metrics import batched_ncg

__all__ = ["SystemConfig", "RetrievalSystem"]


@dataclasses.dataclass(frozen=True)
class SystemConfig:
    corpus: CorpusConfig = CorpusConfig()
    querylog: QueryLogConfig = QueryLogConfig()
    block_docs: int = 512
    max_candidates: int = 512
    n_top: int = 5                      # paper: n = 5
    p_bins: int = 1024                  # paper: 10K (scaled to corpus size)
    u_budget: int = 2048
    t_max: int = 8
    rule_du_scale: int = 1
    rule_dv_scale: int = 1
    l1_hidden: int = 32
    l1_steps: int = 300
    gamma: float = 1.0              # paper: 0 < γ ≤ 1 (undiscounted default)
    seed: int = 0
    # Index-scan strategy for every rollout this system runs (training,
    # baselines, evaluation) — a core/scan_backends.py registry name.
    backend: str = "xla"


class RetrievalSystem:
    # The static system has no live index: one immutable "epoch 0"
    # forever.  `repro.index.live.LiveRetrievalSystem` overrides both
    # with the real IndexEpochStore; serving layers probe these via
    # getattr so they work against either system.
    index_epoch_store = None

    @property
    def index_epoch(self) -> int:
        return 0

    def __init__(self, cfg: SystemConfig,
                 index: Optional[InvertedIndex] = None):
        self.cfg = cfg
        t0 = time.time()
        self.corpus: Corpus = generate_corpus(cfg.corpus)
        # ``index`` injects a pre-built index instead of building one —
        # the process cell hands each worker the parent's saved base
        # generation (np.memmap'd read-only), so N worker processes map
        # ONE physical copy of the postings and skip the build entirely.
        self.index: InvertedIndex = (
            index if index is not None
            else build_index(self.corpus, block_docs=cfg.block_docs))
        self.log: QueryLog = generate_querylog(self.corpus, self.index, cfg.querylog)
        self.ruleset: RuleSet = default_rule_library(cfg.rule_du_scale, cfg.rule_dv_scale)
        self.plans: Dict[str, MatchPlan] = production_plans(self.ruleset)
        self.env_cfg = EnvConfig(
            n_blocks=self.index.n_blocks,
            block_docs=cfg.block_docs,
            k_rules=self.ruleset.k,
            max_candidates=cfg.max_candidates,
            n_top=cfg.n_top,
            u_budget=cfg.u_budget,
        )

        # Device-side per-document side data (padded to block boundary).
        n_pad = self.index.padded_docs
        sr = np.zeros(n_pad, np.float32)
        sr[: self.index.n_docs] = self.index.static_rank
        dl = np.zeros((n_pad, self.index.doc_len.shape[1]), np.float32)
        dl[: self.index.n_docs] = np.log1p(self.index.doc_len) / np.log(256.0)
        self.static_rank = jnp.asarray(sr)
        self.doc_len = jnp.asarray(dl)
        self.idf_all = idf_for_terms(
            self.index.df[:, 2].astype(np.float64), self.index.n_docs, self.log.terms
        )  # body-field df

        self.l1_params = init_l1(jax.random.key(cfg.seed), hidden=cfg.l1_hidden)
        self.bins: Optional[StateBins] = None
        self.qcfg: Optional[QConfig] = None
        self.build_time = time.time() - t0

    # ---------------------------------------------------------------- batches
    def batch_inputs(self, query_ids: Sequence[int], epoch=None,
                     span=NULL_SPAN):
        """Occupancy + L1 scores + masks for a set of query ids.

        ``epoch`` exists for signature parity with the live system's
        epoch-pinned batches; the static index ignores it."""
        return self._device_inputs(
            query_ids, partial(batch_query_occupancy, self.index),
            self.static_rank, self.doc_len, span)

    def _device_inputs(self, query_ids: Sequence[int], occupancy,
                       static_rank, doc_len, span=NULL_SPAN):
        """``(occ, scores, term_present)`` of one batch, as children of
        ``span``: ``occupancy(term_lists, span)`` builds the host bitmaps
        (span ``occupancy``), which are copied to the device with the
        term mask and idf (``h2d``, arg ``bytes``), and the eager L1
        scoring of every document is enqueued (``l1_dispatch``)."""
        qids = np.asarray(query_ids)
        log = self.log                      # capture refs: live appends swap
        idf_all = self.idf_all              # whole arrays, never resize
        term_lists = [log.terms[q, : log.n_terms[q]] for q in qids]
        with span.child("occupancy", n_queries=len(qids)) as occ_span:
            occ_host = occupancy(term_lists, occ_span)
        present_host = log.terms[qids] >= 0
        idf_host = idf_all[qids]
        with span.child("h2d", bytes=occ_host.nbytes + present_host.nbytes
                        + idf_host.nbytes):
            occ = jnp.asarray(occ_host)
            term_present = jnp.asarray(present_host)
            idf = jnp.asarray(idf_host)
        with span.child("l1_dispatch"):
            params = self.l1_params
            scores = jax.vmap(
                lambda o, i, t: score_all_docs(params, o, i, t, static_rank,
                                               doc_len)
            )(occ, idf, term_present)
        return occ, scores, term_present

    def judged(self, query_ids: Sequence[int]):
        qids = np.asarray(query_ids)
        return (
            jnp.asarray(self.log.judged_ids[qids]),
            jnp.asarray(self.log.judged_gains[qids]),
        )

    # ------------------------------------------------------------------- L1
    def fit_l1(self, n_queries: int = 256, batch: int = 32):
        """Train the L1 ranker on judged (query, doc) pairs."""
        rng = np.random.default_rng(self.cfg.seed + 1)
        qids = rng.choice(self.log.n_queries, size=min(n_queries, self.log.n_queries), replace=False)
        feats_l, gains_l = [], []
        for i in range(0, len(qids), batch):
            chunk = qids[i : i + batch]
            occ, _, term_present = self.batch_inputs(chunk)
            idf = jnp.asarray(self.idf_all[chunk])
            feats = jax.vmap(
                lambda o, i_, t: doc_features(o, i_, t, self.static_rank, self.doc_len)
            )(occ, idf, term_present)
            jids = self.log.judged_ids[chunk]
            for row, q in enumerate(chunk):
                mask = jids[row] >= 0
                ids = np.clip(jids[row], 0, None)
                feats_l.append(np.asarray(feats[row])[ids][mask])
                gains_l.append(self.log.judged_gains[q][mask])
        feats = np.concatenate(feats_l)
        gains = np.concatenate(gains_l)
        weights = 1.0 + gains.astype(np.float32)  # emphasize relevant docs
        self.l1_params, losses = train_l1(
            self.l1_params, feats, gains, weights, steps=self.cfg.l1_steps, seed=self.cfg.seed
        )
        return losses

    # ------------------------------------------------------------- baselines
    def plan_for_category(self, cat: int) -> MatchPlan:
        return self.plans["CAT2" if cat == CAT2 else "CAT1"]

    def plan_policy(self, cat: int) -> StaticPlanPolicy:
        """The hand-tuned production plan as a first-class Policy."""
        return StaticPlanPolicy(self.plan_for_category(cat), self.env_cfg.n_actions)

    def shallow_plan(self, cat: int, length: int = 2) -> MatchPlan:
        """Truncated production plan served at ServiceLevel.SHALLOW —
        u bounded by the prefix's summed Δu quotas."""
        return self.plan_for_category(cat).prefix(length)

    def shallow_u_cap(self, cat: int, length: int = 2) -> int:
        """Worst-case u of ONE single-shard shallow-plan execution:
        summed Δu quotas plus one block's planes of quota overshoot per
        entry.  The honest per-query bound degraded serving promises."""
        from repro.index.builder import MAX_QUERY_TERMS
        from repro.index.corpus import N_FIELDS
        return self.shallow_plan(cat, length).u_cap(
            per_entry_overshoot=MAX_QUERY_TERMS * N_FIELDS)

    def fallback_policies(self, cats: Sequence[int] = (CAT1, CAT2),
                          length: int = 2) -> Dict[int, StaticPlanPolicy]:
        """Degraded-service fallbacks published alongside live snapshots
        (PolicyStore.publish(policies, fallbacks=...))."""
        return {cat: StaticPlanPolicy(self.shallow_plan(cat, length),
                                      self.env_cfg.n_actions)
                for cat in cats}

    def _run_plan_batch(self, plan: MatchPlan, occ, scores, term_present):
        """Batched static-plan execution via the unified rollout; returns
        (final_state, trajectory with (B, L) leaves)."""
        return plan_rollout(self.env_cfg, self.ruleset, plan,
                            occ, scores, term_present,
                            backend=self.cfg.backend)

    def run_baseline(self, query_ids: Sequence[int], cat: int):
        occ, scores, term_present = self.batch_inputs(query_ids)
        plan = self.plan_for_category(cat)
        final, traj = self._run_plan_batch(plan, occ, scores, term_present)
        return final, traj, (occ, scores, term_present)

    def production_step_rewards(self, traj) -> jnp.ndarray:
        """Per-step r_agent of the production plan (Eq. 4's subtrahend)."""
        u = jnp.maximum(traj["u"], 1).astype(jnp.float32)          # (B?, L) — scan stacks on axis 0
        # plan_rollout vmaps over queries: traj leaves are (B, L)
        v = traj["v"].astype(jnp.float32)
        m = jnp.clip(jnp.minimum(v, self.env_cfg.n_top), 1, self.env_cfg.n_top)
        return traj["topn_sum"] / (m * u)

    # ------------------------------------------------------------------ bins
    def fit_state_bins(self, n_queries: int = 256, batch: int = 64):
        """Harvest (u, v) from baseline runs; fit equal-mass bins."""
        rng = np.random.default_rng(self.cfg.seed + 2)
        us, vs = [], []
        for cat in (CAT1, CAT2):
            qids_all = np.where(self.log.category == cat)[0]
            qids = rng.choice(qids_all, size=min(n_queries, len(qids_all)), replace=False)
            for i in range(0, len(qids), batch):
                _, traj, _ = self.run_baseline(qids[i : i + batch], cat)
                us.append(np.asarray(traj["u"]).ravel())
                vs.append(np.asarray(traj["v"]).ravel())
        self.bins = fit_bins(np.concatenate(us), np.concatenate(vs), p=self.cfg.p_bins)
        self.qcfg = QConfig(
            p=self.bins.p, n_actions=self.env_cfg.n_actions, t_max=self.cfg.t_max,
            gamma=self.cfg.gamma,
        )
        return self.bins

    # -------------------------------------------------------------- training
    def sample_train_qids(self, cat: int, batch: int,
                          rng: np.random.Generator) -> np.ndarray:
        """One training batch of query ids for a category (with
        replacement — shared by the offline and online trainers)."""
        qids_all = np.where(self.log.category == cat)[0]
        return rng.choice(qids_all, size=min(batch, len(qids_all)),
                          replace=True)

    def policy_train_step(self, cat: int, q: jnp.ndarray, key, eps: float,
                          qids: Sequence[int]):
        """One ε-greedy Q-learning iteration on a batch of query ids:
        production-plan rollout for Eq. 4's reward baseline, then
        ``train_batch``.  Returns (q, metrics).  This is the unit an
        online trainer loop (src/repro/cluster/trainer.py) interleaves
        with snapshot publishes."""
        assert self.bins is not None, "fit_state_bins() first"
        occ, scores, term_present = self.batch_inputs(qids)
        plan = self.plan_for_category(cat)
        _, traj = self._run_plan_batch(plan, occ, scores, term_present)
        prod_r = self.production_step_rewards(traj)
        return train_batch(
            self.env_cfg, self.qcfg, self.ruleset, self.bins, q,
            occ, scores, term_present, prod_r, jnp.float32(eps), key,
            backend=self.cfg.backend,
        )

    def train_policy(
        self,
        cat: int,
        iters: int = 150,
        batch: int = 64,
        eps_start: float = 0.5,
        eps_end: float = 0.05,
        seed: int = 0,
        log_every: int = 0,
    ):
        """Tabular Q-learning for one query category (paper trains separate
        policies per category)."""
        assert self.bins is not None, "fit_state_bins() first"
        rng_np = np.random.default_rng(seed)
        q = init_q(self.qcfg)
        key = jax.random.key(seed)
        history = []
        for it in range(iters):
            qids = self.sample_train_qids(cat, batch, rng_np)
            eps = linear_epsilon(it, iters, eps_start, eps_end)
            key, sub = jax.random.split(key)
            q, metrics = self.policy_train_step(cat, q, sub, eps, qids)
            history.append({k: float(v) for k, v in metrics.items()})
            if log_every and (it % log_every == 0):
                print(f"[cat{cat}] iter {it:4d} eps {eps:.2f} " +
                      " ".join(f"{k}={v:.4f}" for k, v in history[-1].items()))
        return q, history

    # ------------------------------------------------------------ policies
    def train_policy_store(self, cats: Sequence[int] = (CAT1, CAT2),
                           store: Optional[PolicyStore] = None,
                           staleness_bound: int = 1,
                           **train_kwargs) -> PolicyStore:
        """Train per-category tabular policies and publish one snapshot.
        Pass an existing ``store`` to publish a fresh version into it
        (the serve-while-training loop)."""
        policies = {cat: TabularQPolicy(self.train_policy(cat, **train_kwargs)[0])
                    for cat in cats}
        if store is None:
            store = PolicyStore(staleness_bound=staleness_bound)
        store.publish(policies)
        return store

    def baseline_policies(self, cats: Sequence[int] = (CAT1, CAT2)):
        """The hand-tuned production plans as a {category: Policy} dict."""
        return {cat: self.plan_policy(cat) for cat in cats}

    # ------------------------------------------------------------ evaluation
    def evaluate(self, q: jnp.ndarray, query_ids: Sequence[int], cat: int):
        """Learned policy vs production plan on the same queries.
        Returns per-query arrays for NCG@100 and blocks accessed u."""
        occ, scores, term_present = self.batch_inputs(query_ids)
        judged_ids, judged_gains = self.judged(query_ids)

        plan = self.plan_for_category(cat)
        base_final, _ = self._run_plan_batch(plan, occ, scores, term_present)
        pol_res = unified_rollout(
            self.env_cfg, self.ruleset, self.bins, TabularQPolicy(q),
            self.qcfg.t_max, occ, scores, term_present,
            backend=self.cfg.backend,
        )
        pol_final, actions = pol_res.final_state, pol_res.transitions["a"]

        out = {}
        for name, fin in (("baseline", base_final), ("policy", pol_final)):
            out[f"{name}_ncg"] = np.asarray(batched_ncg(fin.cand, judged_ids, judged_gains))
            out[f"{name}_u"] = np.asarray(fin.u)
            out[f"{name}_cand"] = np.asarray(fin.cand_cnt)
        out["actions"] = np.asarray(actions)
        return out
