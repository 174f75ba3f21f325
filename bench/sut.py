"""The system under test, built from a configuration file and a seed.

The program builds the corpus, the index and the query log from the
seed; the benchmark then installs the tables it made
(``bench/weights.py``) in place of the program's own fits, so that the
plain reference can serve the same queries without taking anything the
program made.  The window drives ``ReplicaSet.submit``: admission,
router, a replica's worker thread, the engine's batcher and result
cache, ``System.batch_inputs`` and the AOT serve executable.
"""
from __future__ import annotations

import sys

import numpy as np

from .spec import ROOT

if str(ROOT / "src") not in sys.path:
    sys.path.insert(0, str(ROOT / "src"))


def build_system(cfg: dict, seed: int, weights: dict):
    """``(system, policy store)`` at the configuration's sizes."""
    import jax.numpy as jnp

    from repro.core.qlearning import QConfig
    from repro.core.state_bins import StateBins
    from repro.data.querylog import QueryLogConfig
    from repro.index.corpus import CorpusConfig
    from repro.policies import PolicyStore, TabularQPolicy
    from repro.system import RetrievalSystem, SystemConfig

    w = cfg["widths"]
    n_docs = int(cfg["n_blocks"]) * int(w["block_docs"])
    sys_ = RetrievalSystem(SystemConfig(
        corpus=CorpusConfig(n_docs=n_docs, seed=seed, **cfg["corpus"]),
        querylog=QueryLogConfig(seed=seed, **cfg["querylog"]),
        block_docs=int(w["block_docs"]),
        max_candidates=int(w["max_candidates"]), n_top=int(w["n_top"]),
        p_bins=int(w["p_bins"]), u_budget=int(w["u_budget"]),
        t_max=int(w["t_max"]), l1_hidden=int(w["l1_hidden"]), seed=seed,
        backend=cfg["engine"]["backend"]))
    check_rules(sys_.ruleset, cfg["rules"])
    sys_.l1_params = {k: jnp.asarray(v) for k, v in weights["l1"].items()}
    sys_.bins = StateBins(u_edges=jnp.asarray(weights["u_edges"]),
                          v_edges=jnp.asarray(weights["v_edges"]))
    sys_.qcfg = QConfig(p=sys_.bins.p, n_actions=sys_.env_cfg.n_actions,
                        t_max=int(w["t_max"]), gamma=sys_.cfg.gamma)
    store = PolicyStore(staleness_bound=1)
    store.publish({cat: TabularQPolicy(jnp.asarray(weights["q"][cat]))
                   for cat in range(int(cfg["n_categories"]))})
    return sys_, store


def check_rules(ruleset, rules: dict) -> None:
    """The program's rule library must be the one the configuration
    states (and the reference runs)."""
    for key in ("allowed", "required", "du_quota", "dv_quota"):
        got = np.asarray(getattr(ruleset, key))
        want = np.asarray(rules[key], got.dtype)
        if got.shape != want.shape or not np.array_equal(got, want):
            raise ValueError(f"the program's rule library differs from the "
                             f"configuration's in {key!r}")


def replica_set(sys_, store, cfg: dict, tracer):
    from repro.cluster import ClusterConfig, ReplicaSet
    from repro.serving import EngineConfig

    e, c = cfg["engine"], cfg["cluster"]
    budget = c["u_inflight_budget"]
    return ReplicaSet(
        sys_, store,
        ClusterConfig(n_replicas=int(c["n_replicas"]), backend=c["backend"],
                      u_inflight_budget=(float("inf") if budget is None
                                         else float(budget))),
        EngineConfig(min_bucket=int(e["min_bucket"]),
                     max_bucket=int(e["max_bucket"]),
                     cache_capacity=int(e["cache_capacity"]),
                     n_shards=int(e["n_shards"]),
                     keep=int(cfg["widths"]["keep"]), backend=e["backend"]),
        tracer=tracer)


def buckets(cfg: dict):
    b, out = int(cfg["engine"]["min_bucket"]), []
    while b <= int(cfg["engine"]["max_bucket"]):
        out.append(b)
        b *= 2
    return out


def warmup_queries(log, cfg: dict, seed: int):
    """One category-pure group of query ids per bucket size, all with
    distinct cache keys, so that each warm-up drain fills exactly one
    bucket of each size the window can use."""
    rng = np.random.default_rng([seed, 3])
    groups, seen = [], set()
    cats = sorted({int(c) for c in log.category})
    for i, b in enumerate(buckets(cfg)):
        cat = cats[i % len(cats)]
        group = []
        for q in rng.permutation(np.flatnonzero(log.category == cat)):
            key = (cat, tuple(sorted(int(t) for t in log.terms[q] if t >= 0)))
            if key not in seen:
                seen.add(key)
                group.append(int(q))
            if len(group) == b:
                break
        groups.append(group)
    return groups


def is_response(result) -> bool:
    from repro.serving.engine import ServeResponse

    return isinstance(result, ServeResponse)
