"""The system under test, built from a configuration file and a seed.

The program builds the corpus, the index and the query log from the
seed; the benchmark then installs the tables it made
(``bench/weights.py``) in place of the program's own fits, so that the
plain reference can serve the same queries without taking anything the
program made.  Where the configuration has a ``served_log`` section, the
benchmark also extends the program's judged log with unjudged queries
(``extend_log``), so that a fast server never runs out of queries it
has not seen.  The window drives ``ReplicaSet.submit``: admission,
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
    from repro.ranking.l1_ranker import idf_for_terms
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
    if "served_log" in cfg:
        sys_.log = extend_log(sys_.log, sys_.corpus,
                              int(cfg["served_log"]["n_queries"]), cfg, seed)
        sys_.idf_all = idf_for_terms(sys_.index.df[:, 2].astype(np.float64),
                                     sys_.index.n_docs, sys_.log.terms)
    sys_.l1_params = {k: jnp.asarray(v) for k, v in weights["l1"].items()}
    sys_.bins = StateBins(u_edges=jnp.asarray(weights["u_edges"]),
                          v_edges=jnp.asarray(weights["v_edges"]))
    sys_.qcfg = QConfig(p=sys_.bins.p, n_actions=sys_.env_cfg.n_actions,
                        t_max=int(w["t_max"]), gamma=sys_.cfg.gamma)
    store = PolicyStore(staleness_bound=1)
    store.publish({cat: TabularQPolicy(jnp.asarray(weights["q"][cat]))
                   for cat in range(int(cfg["n_categories"]))})
    return sys_, store


def extend_log(log, corpus, n_queries: int, cfg: dict, seed: int):
    """``log`` followed by unjudged queries up to ``n_queries`` in all.

    The extension follows the sampling law of the program's
    ``repro.data.querylog.generate_querylog``, from its own stream of the
    seed: a CAT2 query takes 2-3 terms of the title and url terms of one
    of the ``max(64, n_docs // 16)`` best-ranked documents; a CAT1 query
    takes 3 to ``MAX_QUERY_TERMS`` terms of a random document's body
    terms within its topic, or of its body terms where fewer than 2 are
    topical.  The program's generator cannot be reused: it draws each
    query's terms and judgements from one stream in turn, and it judges
    every document for every query, which would cost set-up minutes.
    Extension queries are not judged (``judged_ids`` -1,
    ``judged_gains`` 0); nothing served reads judgements.  Popularity is
    the program's Zipf over the whole log, CAT2 queries first.
    """
    from repro.data.querylog import CAT1, CAT2, QueryLog
    from repro.index.builder import MAX_QUERY_TERMS
    from repro.index.corpus import B, T, U

    n = n_queries - log.n_queries
    if n < 0:
        raise ValueError(f"served_log.n_queries {n_queries} is below the "
                         f"judged log's {log.n_queries}")
    qcfg = cfg["querylog"]
    rng = np.random.default_rng([seed, 4])
    top_pool = max(64, corpus.n_docs // 16)
    terms = np.full((n, MAX_QUERY_TERMS), -1, np.int32)
    n_terms = np.zeros(n, np.int32)
    category = np.zeros(n, np.int8)
    seed_doc = np.zeros(n, np.int32)
    for i in range(n):
        if rng.random() < float(qcfg["frac_cat2"]):
            d = int(rng.integers(0, top_pool))
            pool = np.union1d(corpus.field_terms[T][d], corpus.field_terms[U][d])
            nt = int(rng.integers(2, 4))
            category[i] = CAT2
        else:
            d = int(rng.integers(0, corpus.n_docs))
            pool = np.intersect1d(corpus.field_terms[B][d],
                                  corpus.topic_terms[corpus.doc_topic[d]])
            if len(pool) < 2:
                pool = corpus.field_terms[B][d]
            nt = int(rng.integers(3, MAX_QUERY_TERMS + 1))
            category[i] = CAT1
        qt = rng.choice(pool, size=max(min(nt, len(pool)), 1), replace=False)
        terms[i, :len(qt)] = qt
        n_terms[i] = len(qt)
        seed_doc[i] = d
    n_judged = log.judged_ids.shape[1]
    cat_all = np.concatenate([log.category, category])
    ranks = np.empty(len(cat_all), np.int64)
    ranks[np.argsort(cat_all)[::-1]] = np.arange(len(cat_all))
    pop = (1.0 + ranks) ** -float(qcfg["zipf_a"])
    return QueryLog(
        terms=np.concatenate([log.terms, terms]),
        n_terms=np.concatenate([log.n_terms, n_terms]),
        popularity=pop / pop.sum(),
        category=cat_all,
        judged_ids=np.concatenate(
            [log.judged_ids, np.full((n, n_judged), -1, np.int32)]),
        judged_gains=np.concatenate(
            [log.judged_gains, np.zeros((n, n_judged), np.int8)]),
        seed_doc=np.concatenate([log.seed_doc, seed_doc]))


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
