"""Serving driver: a thin CLI over `repro.serving.ServeEngine`.

Trains the L0 policies + L1 ranker inline (as the seed driver did),
then streams query batches through the online engine — admission →
result cache → shape-bucketed micro-batching → pre-compiled per-shard
rollout → L1 prune — with latency accounting both in wall time and in
index blocks (u), the unit the paper shows is linear in machine time.

    PYTHONPATH=src python -m repro.launch.serve --batches 4 --batch 64
    python -m repro.launch.serve --arch websearch-rl --n-docs 1048576 ...

``--arch websearch-rl`` serves at that arch's published widths
(block_docs=4096, p_bins=10000, u_budget=65536; configs/websearch_rl.py);
without it the CLI keeps its own CPU-sized widths.

Output keeps the seed schema (one JSON row per batch with t_inputs_s /
t_serve_s / mean_u / p99_u / qps_host) and adds engine fields
(cache hits, compile counts, latency percentiles) plus a trailing
engine summary at results/serve_summary.json.
"""
from __future__ import annotations

import argparse
import json
import time
from pathlib import Path
from typing import Optional

import numpy as np


# The CLI's own CPU-sized widths, used when no --arch is given.
CLI_WIDTHS = dict(block_docs=256, p_bins=1024, u_budget=1024)


def arch_widths(arch: Optional[str]) -> dict:
    """SystemConfig widths: an arch's published ones (``configs/``), or
    the CLI's CPU-sized defaults when ``arch`` is None."""
    if arch is None:
        return dict(CLI_WIDTHS)
    from repro.configs import get_arch
    from repro.core.match_rules import default_rule_library

    wcfg = get_arch(arch).model_cfg(False)
    if wcfg.k_rules != default_rule_library().k:
        raise ValueError(f"{arch}: k_rules={wcfg.k_rules} but the rule "
                         f"library has {default_rule_library().k} rules")
    return dict(block_docs=wcfg.block_docs, max_candidates=wcfg.max_candidates,
                n_top=wcfg.n_top, p_bins=wcfg.p_bins, t_max=wcfg.t_max,
                u_budget=wcfg.u_budget)


def build_system(*, arch: Optional[str] = None, n_docs: int = 8192,
                 n_queries: int = 1000, iters: int = 120,
                 train_batch: int = 48, l1_queries: int = 128,
                 bins_queries: int = 96, l1_steps: int = 250,
                 fit_batch: Optional[int] = None, seed: int = 0):
    """Build the index, fit L1 and the state bins, and train the
    per-category policies into a PolicyStore (snapshot v1).  Returns
    ``(system, store, seconds per phase)``.  ``fit_batch`` caps the
    query batch of the L1 and bin fits, whose device memory grows with
    batch x documents."""
    from repro.data.querylog import CAT1, CAT2, QueryLogConfig
    from repro.index.corpus import CorpusConfig
    from repro.system import RetrievalSystem, SystemConfig

    secs = {}
    t0 = time.perf_counter()
    sys_ = RetrievalSystem(SystemConfig(
        corpus=CorpusConfig(n_docs=n_docs, vocab_size=2048, seed=seed),
        querylog=QueryLogConfig(n_queries=n_queries, seed=seed),
        l1_steps=l1_steps, seed=seed, **arch_widths(arch),
    ))
    secs["build_index"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    fit_kw = {} if fit_batch is None else {"batch": fit_batch}
    sys_.fit_l1(n_queries=l1_queries, **fit_kw)
    secs["fit_l1"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    sys_.fit_state_bins(n_queries=bins_queries, **fit_kw)
    secs["fit_state_bins"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    # Trained tabular policies published as snapshot v1 of a PolicyStore;
    # the engine pins the snapshot and would pick up any later publish.
    store = sys_.train_policy_store(cats=(CAT1, CAT2),
                                    iters=iters, batch=train_batch)
    secs["train_policy_store"] = time.perf_counter() - t0
    return sys_, store, secs


def main() -> None:
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None, choices=["websearch-rl"],
                    help="serve at this arch's published widths "
                         "(block_docs, p_bins, u_budget, ...) instead of "
                         "the CLI's CPU-sized ones")
    ap.add_argument("--n-docs", type=int, default=8192)
    ap.add_argument("--n-queries", type=int, default=1000)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--batches", type=int, default=4)
    ap.add_argument("--iters", type=int, default=120)
    ap.add_argument("--fit-batch", type=int, default=None,
                    help="query batch of the L1 and state-bin fits "
                         "(cap it at large --n-docs: device memory grows "
                         "with batch x documents)")
    ap.add_argument("--out", default="results/serve.json")
    ap.add_argument("--min-bucket", type=int, default=8)
    ap.add_argument("--max-bucket", type=int, default=64)
    ap.add_argument("--cache", type=int, default=4096,
                    help="result-cache capacity (0 disables)")
    ap.add_argument("--shards", type=int, default=1,
                    help="logical index shards for scatter-gather serving")
    ap.add_argument("--backend", default="xla",
                    help="rollout backend (see repro.serving.available_backends)")
    ap.add_argument("--trace-out", default=None,
                    help="write a Chrome trace-event JSON (Perfetto-"
                         "loadable) of the serving run to this path")
    ap.add_argument("--metrics-json", default=None,
                    help="write the engine's metrics-registry snapshot "
                         "to this path")
    args = ap.parse_args()

    from repro.obs import NULL_TRACER, Tracer
    from repro.serving import EngineConfig, ServeEngine

    tracer = Tracer() if args.trace_out else NULL_TRACER

    sys_, store, _ = build_system(
        arch=args.arch, n_docs=args.n_docs, n_queries=args.n_queries,
        iters=args.iters, fit_batch=args.fit_batch)

    engine = ServeEngine(sys_, store, EngineConfig(
        min_bucket=args.min_bucket, max_bucket=args.max_bucket,
        cache_capacity=args.cache, n_shards=args.shards,
        backend=args.backend), tracer=tracer)
    n_compiles_warm = engine.warmup()
    print(f"warmup: {n_compiles_warm} bucket executables compiled "
          f"(policy snapshot v{engine.policy_version})")

    stats = []
    rng = np.random.default_rng(0)
    for bi in range(args.batches):
        qids = rng.integers(0, sys_.log.n_queries, size=args.batch)
        t0 = time.time()
        rids = [engine.submit(int(q)) for q in qids]
        t_inputs = time.time() - t0          # admission + cache lookups
        t0 = time.time()
        engine.flush()
        t_serve = time.time() - t0
        res = [engine.take_response(r) for r in rids]

        u_all = np.array([r.u for r in res], np.float64)
        lat = np.array([r.latency_s for r in res], np.float64)
        stats.append({
            "batch": bi, "t_inputs_s": t_inputs, "t_serve_s": t_serve,
            "mean_u": float(u_all.mean()),
            "p99_u": float(np.quantile(u_all, 0.99)),
            "qps_host": args.batch / (t_inputs + t_serve),
            # engine-specific fields (new in the serving subsystem)
            "n_cached": sum(r.cached for r in res),
            "latency_p50_ms": float(np.quantile(lat, 0.50)) * 1e3,
            "latency_p99_ms": float(np.quantile(lat, 0.99)) * 1e3,
            "compiles_cum": engine.compile_count,
        })
        print(stats[-1])

    summary = engine.summary()
    print("engine summary:", json.dumps(summary, indent=1))

    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    Path(args.out).write_text(json.dumps(stats, indent=1))
    Path(args.out).with_name("serve_summary.json").write_text(
        json.dumps(summary, indent=1))
    if args.trace_out:
        tracer.log.write_chrome(args.trace_out, process_name="repro-serve")
        print(f"trace: {len(tracer.log)} events -> {args.trace_out} "
              f"(open at ui.perfetto.dev)")
    if args.metrics_json:
        p = Path(args.metrics_json)
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(json.dumps(engine.telemetry.registry.snapshot(),
                                indent=1))
        print(f"metrics: registry snapshot -> {args.metrics_json}")


if __name__ == "__main__":
    main()
