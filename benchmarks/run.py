"""Benchmark entry point — one section per paper table/figure plus the
framework's own perf surfaces.  Prints ``name,us_per_call,derived`` CSV
(plus the Table-1/Figure-2 summaries).

    PYTHONPATH=src python -m benchmarks.run             # fast set
    PYTHONPATH=src python -m benchmarks.run --full      # + Table 1 retrain
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="retrain policies for Table 1 (slower)")
    ap.add_argument("--serve-bench", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="run the serving engine benchmark "
                         "(--no-serve-bench to skip)")
    ap.add_argument("--cluster-bench", action=argparse.BooleanOptionalAction,
                    default=False,
                    help="run the online-learning cluster benchmark "
                         "(replica scaling / routing / shedding)")
    ap.add_argument("--index-bench", action=argparse.BooleanOptionalAction,
                    default=False,
                    help="run the tiered live-index benchmark (>= 1M-doc "
                         "build/ingest/merge + bytes-per-query per backend)")
    args = ap.parse_args()

    from benchmarks._results import record

    print("== kernel microbenchmarks ==")
    from benchmarks import kernel_bench
    kernel_bench.main()

    print("\n== match-plan executor (unified_rollout, StaticPlanPolicy) ==")
    import jax
    import numpy as np

    from repro.core.rollout import unified_rollout
    from repro.index.corpus import CorpusConfig
    from repro.data.querylog import QueryLogConfig
    from repro.policies import StaticPlanPolicy
    from repro.system import RetrievalSystem, SystemConfig

    sys_ = RetrievalSystem(SystemConfig(
        corpus=CorpusConfig(n_docs=4096, vocab_size=1024, seed=1),
        querylog=QueryLogConfig(n_queries=256, seed=1),
        block_docs=256, p_bins=256, l1_steps=50,
    ))
    qids = np.arange(64)
    occ, scores, tp = sys_.batch_inputs(qids)
    plan = sys_.plans["CAT2"]
    policy = StaticPlanPolicy(plan, sys_.env_cfg.n_actions)
    fn = lambda: jax.block_until_ready(
        unified_rollout(sys_.env_cfg, sys_.ruleset, None, policy, plan.length,
                        occ, scores, tp).final_state.u)
    fn()
    t0 = time.time()
    for _ in range(5):
        fn()
    us = (time.time() - t0) / 5 * 1e6
    print(f"plan_executor_64q_4096d,{us:.0f},{us/64:.0f}us_per_query_host")
    record("plan_executor",
           config={"n_docs": 4096, "batch": 64, "plan": "CAT2"},
           metrics={"us_per_call": us, "us_per_query_host": us / 64})

    if args.serve_bench:
        print("\n== serving engine (QPS / p99 / steady-state retraces) ==")
        from benchmarks import serve_bench
        serve_bench.main(fast=not args.full)
    else:
        print("\n(serving engine benchmark skipped: --no-serve-bench)")

    if args.cluster_bench:
        print("\n== online-learning cluster (replicas / routing / shedding) ==")
        from benchmarks import cluster_bench
        cluster_bench.main(fast=not args.full,
                           replicas_list=(1, 2) if not args.full else (1, 2, 4))
    else:
        print("\n(cluster benchmark skipped: pass --cluster-bench, "
              "or `make cluster-bench`)")

    if args.index_bench:
        print("\n== tiered live index (build / ingest / merge / bytes) ==")
        from benchmarks import index_bench
        index_bench.main(fast=not args.full)
    else:
        print("\n(live-index benchmark skipped: pass --index-bench, "
              "or `make index-bench`)")

    # Table 1 / Figure 2
    if args.full:
        print("\n== Table 1 (retraining policies) ==")
        from benchmarks import table1
        table1.main("small")
        print("\n== Figure 2 ==")
        from benchmarks import figure2
        figure2.main()
    else:
        p = Path("results/table1.json")
        if p.exists():
            print("\n== Table 1 (cached results/table1.json) ==")
            for r in json.loads(p.read_text())["rows"]:
                print(r)
        else:
            print("\n(Table 1: run with --full or `python -m benchmarks.table1`)")

    # Roofline summary from the dry-run
    rp = Path("results/roofline.json")
    if rp.exists():
        print("\n== roofline (from dry-run; see EXPERIMENTS.md §Roofline) ==")
        rows = json.loads(rp.read_text())
        for r in rows:
            print(f"{r['arch']},{r['shape']},bound={r['bound']},"
                  f"compute_s={r['compute_s']:.3e},memory_s={r['memory_s']:.3e},"
                  f"collective_s={r['collective_s']:.3e}")


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
