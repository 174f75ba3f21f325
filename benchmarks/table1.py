"""Table 1 reproduction: ΔNCG@100 and Δu of the learned policy vs the
production match plans, per category × weighted/unweighted eval set.

Paper numbers (the envelope we validate against):
    CAT1 weighted:   NCG −1.8%, blocks −17.5%
    CAT1 unweighted: NCG −6.2%, blocks −16.3%
    CAT2 weighted:   NCG +0.2%, blocks −22.7%
    CAT2 unweighted: coverage too low to report

Our system is synthetic-data (DESIGN.md §5); the claim validated is the
*shape* of the trade: double-digit relative block reduction at
single-digit |ΔNCG|, per category, statistically significant.
"""
from __future__ import annotations

import json
import time
from pathlib import Path

import numpy as np

from repro.data.querylog import CAT1, CAT2, QueryLogConfig, sample_eval_sets
from repro.index.corpus import CorpusConfig
from repro.ranking.metrics import paired_permutation_pvalue, relative_delta
from repro.system import RetrievalSystem, SystemConfig


def build_system(scale: str = "small") -> RetrievalSystem:
    if scale == "small":
        cfg = SystemConfig(
            corpus=CorpusConfig(n_docs=8192, vocab_size=2048, seed=0),
            querylog=QueryLogConfig(n_queries=1200, seed=0),
            block_docs=256, p_bins=1024, u_budget=8192, l1_steps=2500,
            rule_du_scale=8, rule_dv_scale=50, l1_hidden=64, t_max=10,
        )
        sys_ = RetrievalSystem(cfg)
        sys_.fit_l1(n_queries=384, batch=24)
        sys_.fit_state_bins(n_queries=128, batch=32)
    else:
        cfg = SystemConfig(
            corpus=CorpusConfig(n_docs=16384, vocab_size=4096, seed=0),
            querylog=QueryLogConfig(n_queries=4000, seed=0),
            block_docs=512, p_bins=4096, u_budget=16384, l1_steps=3000,
            rule_du_scale=12, rule_dv_scale=100, l1_hidden=64, t_max=10,
        )
        sys_ = RetrievalSystem(cfg)
        sys_.fit_l1(n_queries=512, batch=24)
        sys_.fit_state_bins(n_queries=256, batch=32)
    return sys_


def run(sys_: RetrievalSystem, iters: int = 300, train_batch: int = 48,
        n_eval: int = 1024, seed: int = 0):
    rows = []
    per_query = {}
    weighted, unweighted = sample_eval_sets(sys_.log, n_eval, seed=seed)
    for cat, cat_name in ((CAT1, "CAT1"), (CAT2, "CAT2")):
        q, hist = sys_.train_policy(cat, iters=iters, batch=train_batch, seed=seed,
                                    eps_start=0.6, eps_end=0.08)
        for set_name, qids_all in (("weighted", weighted), ("unweighted", unweighted)):
            qids = qids_all[sys_.log.category[qids_all] == cat]
            seg = len(qids) / len(qids_all) * 100.0
            if len(qids) < 12:
                rows.append({"category": cat_name, "set": set_name,
                             "segment_pct": seg, "note": "coverage too low"})
                continue
            res = sys_.evaluate(q, qids, cat)
            d_ncg = relative_delta(res["policy_ncg"], res["baseline_ncg"])
            d_u = relative_delta(res["policy_u"], res["baseline_u"])
            p_ncg = paired_permutation_pvalue(res["policy_ncg"], res["baseline_ncg"])
            p_u = paired_permutation_pvalue(
                res["policy_u"].astype(float), res["baseline_u"].astype(float))
            rows.append({
                "category": cat_name, "set": set_name, "segment_pct": seg,
                "n_queries": int(len(qids)),
                "delta_ncg_pct": d_ncg, "delta_u_pct": d_u,
                "p_ncg": p_ncg, "p_u": p_u,
                "baseline_ncg": float(res["baseline_ncg"].mean()),
                "policy_ncg": float(res["policy_ncg"].mean()),
                "baseline_u": float(res["baseline_u"].mean()),
                "policy_u": float(res["policy_u"].mean()),
            })
            per_query[f"{cat_name}_{set_name}"] = {
                "policy_u": res["policy_u"].tolist(),
                "baseline_u": res["baseline_u"].tolist(),
            }
    return rows, per_query


def main(scale: str = "small", out: str = "results/table1.json"):
    t0 = time.time()
    sys_ = build_system(scale)
    rows, per_query = run(sys_)
    Path(out).parent.mkdir(parents=True, exist_ok=True)
    Path(out).write_text(json.dumps({"rows": rows, "wall_s": time.time() - t0}, indent=1))
    Path(out.replace(".json", "_perquery.json")).write_text(json.dumps(per_query))
    print(f"{'cat':5s} {'set':11s} {'seg%':>6s} {'dNCG%':>7s} {'du%':>7s} {'p_u':>7s}")
    for r in rows:
        if "note" in r:
            print(f"{r['category']:5s} {r['set']:11s} {r['segment_pct']:6.1f} "
                  f"{r['note']}")
        else:
            print(f"{r['category']:5s} {r['set']:11s} {r['segment_pct']:6.1f} "
                  f"{r['delta_ncg_pct']:7.2f} {r['delta_u_pct']:7.2f} {r['p_u']:7.4f}")
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    import sys as _s
    main(scale=_s.argv[1] if len(_s.argv) > 1 else "small")
