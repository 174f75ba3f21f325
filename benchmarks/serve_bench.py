"""Serving benchmark: online engine vs. the seed's naive batch loop.

The seed driver split every arriving batch by query category with a
boolean mask, so the jitted rollout saw a different batch shape almost
every time and retraced continuously.  The engine quantizes shapes into
power-of-two buckets hitting pre-compiled executables, caches repeated
queries, and scatter-gathers across logical index shards.

A second section sweeps the scan backends (``--backends``, default
xla + pallas_block_scan) over the same stream, recording QPS, latency
percentiles, u, and BYTES STREAMED PER QUERY — the bandwidth metric the
plane-pruned backend exists to cut (bytes ∝ u instead of blocks·T·F·W).

Prints ``name,value`` CSV rows and writes results/serve_bench.json in
the shared benchmarks/_results schema:

    PYTHONPATH=src python -m benchmarks.serve_bench            # full
    PYTHONPATH=src python -m benchmarks.serve_bench --fast     # CI size
"""
from __future__ import annotations

import argparse
import time

import numpy as np


def _rollout_cache_size() -> int:
    """Tracing count of the shared unified_rollout jit (version-tolerant).
    Every rollout path — the naive per-category split and the engine's
    bucketed executables — routes through this one scan now."""
    from repro.core.rollout import unified_rollout
    try:
        return int(unified_rollout._cache_size())
    except Exception:
        return -1


def naive_serve_batches(sys_, policies, batches, keep: int = 100):
    """The seed launch/serve.py inner loop, verbatim semantics: one
    variable-size mask split per category per batch."""
    import jax

    from repro.core.rollout import unified_rollout
    from repro.core.telescope import l1_prune
    from repro.data.querylog import CAT1, CAT2

    shapes_seen = set()
    for qids in batches:
        occ, scores, tp = sys_.batch_inputs(qids)
        ids = None
        for cat in (CAT1, CAT2):
            m = sys_.log.category[qids] == cat
            if not m.any():
                continue
            shapes_seen.add((cat, int(m.sum())))
            fin = unified_rollout(sys_.env_cfg, sys_.ruleset, sys_.bins,
                                  policies[cat], sys_.qcfg.t_max,
                                  occ[m], scores[m], tp[m]).final_state
            ids, _ = l1_prune(scores[m], fin.cand, keep=keep)
        if ids is not None:
            jax.block_until_ready(ids)
    return shapes_seen


def engine_serve_batches(engine, batches):
    for qids in batches:
        engine.serve(qids)     # submit + flush + claim responses


def scan_pricing(sys_, policies, qids):
    """Per-lane scan-depth accounting shared by every backend's byte
    model: one xla rollout (the backends are bit-identical, so one
    rollout prices all) yielding, per category mask, the per-step
    scanned-block counts and active-plane counts."""
    from repro.core.rollout import unified_rollout
    from repro.data.querylog import CAT1, CAT2

    qids = np.asarray(qids)
    allowed = np.asarray(sys_.ruleset.allowed)          # (k, T, F)
    k = allowed.shape[0]
    out = []
    for cat in (CAT1, CAT2):
        m = sys_.log.category[qids] == cat
        if not m.any():
            continue
        occ, scores, tp = sys_.batch_inputs(qids[m])
        res = unified_rollout(sys_.env_cfg, sys_.ruleset, sys_.bins,
                              policies[cat], sys_.qcfg.t_max,
                              occ, scores, tp)
        a = np.asarray(res.transitions["a"])            # (S, Bm)
        u = np.asarray(res.trajectory["u"])             # (S, Bm) cumulative
        du = np.diff(u, axis=0, prepend=0)
        tpn = np.asarray(tp)                            # (Bm, T)
        rule = np.clip(a, 0, k - 1)
        n_active = (allowed[rule] & tpn[None, :, :, None]).sum(axis=(2, 3))
        blocks = np.where(n_active > 0, du // np.maximum(n_active, 1), 0)
        out.append((m, blocks, n_active))
    return qids, out


def bytes_streamed_per_query(pricing, sys_, backend: str,
                             chunk: int = 4) -> float:
    """Mean HBM bytes a scan backend streams per query under a PER-LANE
    model over a shared :func:`scan_pricing` result.  "xla" streams the
    full T·F·W tile per block; the pruned backend streams n_active·W
    per block, rounded up to its speculation chunk C.  This is a lower
    bound on real traffic: both backends keep streaming for
    already-stopped lanes until the whole batch's loop exits, and the
    engine pads batches to bucket size — that batch-coupled overhead is
    shared by both and not counted here."""
    qids, per_cat = pricing
    total = np.zeros(len(qids))
    w = sys_.env_cfg.words_per_block
    _, t, f = np.asarray(sys_.ruleset.allowed).shape
    for m, blocks, n_active in per_cat:
        if backend == "pallas_block_scan":
            launched = np.ceil(blocks / chunk) * chunk * (blocks > 0)
            bytes_ = launched * n_active * w * 4
        else:
            bytes_ = blocks * (t * f * w * 4)
        total[m] = bytes_.sum(axis=0)
    return float(total.mean())


def backend_sweep(sys_, policies, batches, backends):
    """Serve the same stream through one engine per scan backend,
    recording QPS / latency / u / bytes-streamed-per-query."""
    from repro.core.scan_backends import DEFAULT_CHUNK_BLOCKS
    from repro.serving import EngineConfig, ServeEngine

    batch = len(batches[0])
    bucket = 1 << (batch - 1).bit_length()
    # One rollout prices every backend's byte model (they're bit-equal).
    pricing = scan_pricing(sys_, policies, np.concatenate(batches[1:]))
    out = {}
    for name in backends:
        engine = ServeEngine(sys_, policies, EngineConfig(
            min_bucket=bucket, max_bucket=bucket, cache_capacity=0,
            backend=name))
        engine.warmup()
        engine_serve_batches(engine, batches[:1])       # post-compile warm
        t0 = time.time()
        engine_serve_batches(engine, batches[1:])
        dt = time.time() - t0
        s = engine.summary()
        out[name] = {
            "qps": batch * (len(batches) - 1) / dt,
            "latency_p50_ms": s["latency_p50_ms"],
            "latency_p99_ms": s["latency_p99_ms"],
            "mean_u": s["mean_u"],
            "p99_u": s["p99_u"],
            "bytes_per_query": bytes_streamed_per_query(
                pricing, sys_, name, chunk=DEFAULT_CHUNK_BLOCKS),
        }
    return out


def obs_overhead(sys_, policies, batches, repeats: int = 3) -> dict:
    """Cost of the observability plane: the same stream through two
    identical engines, one with tracing disabled (the default
    NULL_TRACER — one falsy attribute check per site) and one with a
    live `Tracer` recording the full ticket span chain into the ring.
    Caching is off so every query pays the real rollout, and each mode
    takes its best-of-N wall time to shave scheduler noise.  The gate:
    tracing enabled must cost < 5% QPS."""
    from repro.obs import Tracer
    from repro.serving import EngineConfig, ServeEngine

    batch = len(batches[0])
    bucket = 1 << (batch - 1).bit_length()
    volume = batch * (len(batches) - 1)
    qps, n_events = {}, 0
    for mode in ("tracing_off", "tracing_on"):
        tracer = Tracer() if mode == "tracing_on" else None
        kw = {"tracer": tracer} if tracer is not None else {}
        engine = ServeEngine(sys_, policies, EngineConfig(
            min_bucket=bucket, max_bucket=bucket, cache_capacity=0),
            **kw)
        engine.warmup()
        engine_serve_batches(engine, batches[:1])   # post-compile warm
        best = float("inf")
        for _ in range(repeats):
            t0 = time.time()
            engine_serve_batches(engine, batches[1:])
            best = min(best, time.time() - t0)
        qps[mode] = volume / best
        if tracer is not None:
            n_events = len(tracer.log)
    penalty = 1.0 - qps["tracing_on"] / qps["tracing_off"]
    assert penalty < 0.05, \
        (f"tracing overhead {penalty:.1%} >= 5% "
         f"(off={qps['tracing_off']:.1f} qps, "
         f"on={qps['tracing_on']:.1f} qps)")
    return {
        "qps_tracing_off": qps["tracing_off"],
        "qps_tracing_on": qps["tracing_on"],
        "qps_penalty_frac": penalty,
        "trace_events_recorded": n_events,
    }


def proc_obs_overhead(sys_, policies, batches, repeats: int = 3,
                      n_replicas: int = 2) -> dict:
    """Observability cost across the PROCESS boundary: the same stream
    through two identical 2-worker process cells, one with tracing off
    and one shipping full cross-pid span chains (trace context on every
    ring record, worker-side span recording, delta shipping over the
    control pipe, parent-side rebasing).  Spawn + compile cost is paid
    outside the timed region; each mode takes its best-of-N wall time.
    The gate: the whole cross-process obs plane must cost < 5% QPS."""
    from repro.cluster import ClusterConfig, ReplicaSet
    from repro.obs import NULL_TRACER, Tracer
    from repro.policies import PolicyStore
    from repro.serving import EngineConfig

    batch = len(batches[0])
    bucket = 1 << (batch - 1).bit_length()
    volume = batch * (len(batches) - 1)
    qps, n_entries = {}, 0
    for mode in ("tracing_off", "tracing_on"):
        tracer = Tracer() if mode == "tracing_on" else NULL_TRACER
        store = PolicyStore()
        store.publish(policies)
        cluster = ReplicaSet(sys_, store, ClusterConfig(
            n_replicas=n_replicas, backend="process"),
            EngineConfig(min_bucket=bucket, max_bucket=bucket,
                         cache_capacity=0),
            tracer=tracer)
        with cluster:
            cluster.warmup()
            # The slab front door (`serve_many`) is the hot path now;
            # running the gate through it keeps the <5% obs budget
            # honest for batch-granular arrivals too (traced slabs
            # degrade to per-ticket spans by design — that cost is
            # exactly what this measures).
            for qids in batches[:1]:                # post-compile warm
                cluster.serve_many(qids)
            best = float("inf")
            for _ in range(repeats):
                t0 = time.time()
                for qids in batches[1:]:
                    cluster.serve_many(qids)
                best = min(best, time.time() - t0)
            if mode == "tracing_on":
                n_entries = len(cluster.trace_entries())
        qps[mode] = volume / best
    penalty = 1.0 - qps["tracing_on"] / qps["tracing_off"]
    assert penalty < 0.05, \
        (f"process-cell tracing overhead {penalty:.1%} >= 5% "
         f"(off={qps['tracing_off']:.1f} qps, "
         f"on={qps['tracing_on']:.1f} qps)")
    return {
        "qps_tracing_off": qps["tracing_off"],
        "qps_tracing_on": qps["tracing_on"],
        "qps_penalty_frac": penalty,
        "trace_entries_merged": n_entries,
    }


def build_system(n_docs: int, n_queries: int, iters: int):
    from repro.data.querylog import CAT1, CAT2, QueryLogConfig
    from repro.index.corpus import CorpusConfig
    from repro.policies import TabularQPolicy
    from repro.system import RetrievalSystem, SystemConfig

    sys_ = RetrievalSystem(SystemConfig(
        corpus=CorpusConfig(n_docs=n_docs, vocab_size=1024, seed=0),
        querylog=QueryLogConfig(n_queries=n_queries, seed=0),
        block_docs=256, p_bins=512, u_budget=1024, l1_steps=120,
    ))
    sys_.fit_l1(n_queries=96)
    sys_.fit_state_bins(n_queries=64)
    policies = {cat: TabularQPolicy(sys_.train_policy(cat, iters=iters,
                                                      batch=32)[0])
                for cat in (CAT1, CAT2)}
    return sys_, policies


def main(fast: bool = False,
         backends: str = "xla,pallas_block_scan") -> dict:
    from repro.serving import EngineConfig, ServeEngine

    n_docs = 2048 if fast else 4096
    n_queries = 256 if fast else 512
    iters = 20 if fast else 60
    batch = 32 if fast else 48
    n_batches = 6 if fast else 12
    warm = 2
    backend_list = [b for b in backends.split(",") if b]

    sys_, policies = build_system(n_docs, n_queries, iters)
    rng = np.random.default_rng(7)
    batches = [rng.integers(0, sys_.log.n_queries, size=batch)
               for _ in range(warm + n_batches)]
    volume = batch * n_batches

    # ---------------------------------------------------------- naive loop
    traces0 = _rollout_cache_size()
    naive_serve_batches(sys_, policies, batches[:warm])
    t0 = time.time()
    shapes = naive_serve_batches(sys_, policies, batches[warm:])
    t_naive = time.time() - t0
    naive_traces = (_rollout_cache_size() - traces0) if traces0 >= 0 else -1

    # -------------------------------------------------------------- engine
    engine = ServeEngine(sys_, policies, EngineConfig(
        min_bucket=8, max_bucket=max(8, 1 << (batch - 1).bit_length()),
        cache_capacity=4096, n_shards=1))
    engine.warmup()
    engine_serve_batches(engine, batches[:warm])
    compiles_after_warm = engine.compile_count
    t0 = time.time()
    engine_serve_batches(engine, batches[warm:])
    t_engine = time.time() - t0
    steady_retraces = engine.compile_count - compiles_after_warm

    summary = engine.summary()
    out = {
        "volume_queries": volume,
        "naive_s": t_naive,
        "naive_qps": volume / t_naive,
        "naive_distinct_shapes": len(shapes),
        "naive_rollout_traces": naive_traces,
        "engine_s": t_engine,
        "engine_qps": volume / t_engine,
        "engine_compiles_total": engine.compile_count,
        "engine_steady_state_retraces": steady_retraces,
        "engine_cache_hit_rate": summary["cache_hit_rate"],
        "engine_latency_p50_ms": summary["latency_p50_ms"],
        "engine_latency_p99_ms": summary["latency_p99_ms"],
        "engine_mean_u": summary["mean_u"],
        "engine_peak_queue_depth": summary["peak_queue_depth"],
        "engine_peak_inflight": summary["peak_inflight"],
        "speedup": t_naive / t_engine,
    }
    for k, v in out.items():
        print(f"serve_bench.{k},{v:.4f}" if isinstance(v, float)
              else f"serve_bench.{k},{v}")

    # ----------------------------------------------------- backend sweep
    # Same stream through each scan backend: QPS/latency/u plus the
    # bandwidth story (bytes streamed per query ∝ u for the pruned path,
    # ∝ blocks·T·F·W for full-tile xla).  Wall times on CPU compare an
    # interpret-mode Pallas emulation against compiled XLA, so bytes is
    # the architecture-level metric here.
    sweep = backend_sweep(sys_, policies, batches[: warm + max(2, n_batches // 3)],
                          backend_list)
    out["backends"] = sweep
    for name, row in sweep.items():
        for k, v in row.items():
            print(f"serve_bench.backend.{name}.{k},{v:.4f}")

    # ------------------------------------------------------- obs overhead
    # The tracing plane must be effectively free when off (one falsy
    # attribute check per site) and < 5% QPS when recording full ticket
    # span chains.  Hard-asserted here so a regression fails the bench.
    obs = obs_overhead(sys_, policies,
                       batches[: warm + max(2, n_batches // 3)])
    out["obs"] = obs
    for k, v in obs.items():
        print(f"serve_bench.obs.{k},{v:.4f}" if isinstance(v, float)
              else f"serve_bench.obs.{k},{v}")

    # Same gate across the process boundary: trace context on the ring
    # records + worker span shipping + parent-side merge must also stay
    # under 5% of fleet QPS (the cross-pid plane is the expensive half).
    proc_obs = proc_obs_overhead(sys_, policies,
                                 batches[: warm + max(2, n_batches // 3)])
    out["proc_obs"] = proc_obs
    for k, v in proc_obs.items():
        print(f"serve_bench.proc_obs.{k},{v:.4f}" if isinstance(v, float)
              else f"serve_bench.proc_obs.{k},{v}")

    from benchmarks._results import record
    record("serve_bench",
           config={"fast": fast, "n_docs": n_docs, "n_queries": n_queries,
                   "train_iters": iters, "batch": batch,
                   "n_batches": n_batches, "backends": backend_list},
           metrics=out)
    return out


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--backends", default="xla,pallas_block_scan",
                    help="comma-separated scan backends to sweep "
                         "(see repro.core.scan_backends.available_backends)")
    a = ap.parse_args()
    main(fast=a.fast, backends=a.backends)
