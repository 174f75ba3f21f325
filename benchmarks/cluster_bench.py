"""Cluster benchmark: replica scaling, routing policies, online shedding,
and the graceful-degradation ladder.

Four sections, one results file (shared benchmarks/_results schema):

1. **Routing × replicas** — an open-loop stream whose navigational
   head is LARGER than one replica's result cache but fits the fleet's
   combined caches, plus a rare-term tail.  Queue-aware routing beats
   round-robin on p99 structurally: cache-owner-sticky affinity
   partitions the head across replicas so every repeat hits somewhere,
   while round-robin churns every cache through the full head and
   turns hot repeats into rollouts; tail misses place by per-replica
   depth (in units of likely work).  Runs are PAIRED and order-
   alternated with median-of-repeats p99, because single runs on a
   shared CPU box measure scheduler drift as much as routing.
2. **Online serving** — the largest fleet serves the same stream while
   a `TrainerLoop` publishes snapshots mid-stream: records
   version_lag (observed per response) and hot-swap behaviour.
3. **Admission** — same fleet with a tight u budget: records shed_rate
   and that all non-shed queries complete.
4. **Degradation** — an offered-load sweep (descending pacing down to a
   full burst) against one finite u budget, ladder vs binary shedding:
   per load it records p99, served fraction, candidate recall of the
   served set (SHALLOW-served recall broken out, not silently dropped),
   and the FULL/SHALLOW/CACHED_ONLY/SHED mix.  The ladder must serve a
   >= fraction at every load and strictly more at the burst, while its
   FULL-level responses stay bit-identical to a plain single-engine
   serve of the same queries (degradation never perturbs undegraded
   traffic).

    PYTHONPATH=src python -m benchmarks.cluster_bench --replicas 1,2,4
    PYTHONPATH=src python -m benchmarks.cluster_bench --fast
    PYTHONPATH=src python -m benchmarks.cluster_bench --fast --degradation-only
"""
from __future__ import annotations

import argparse
import time

import numpy as np


HOT_KEYS = 192          # navigational head size (vs CACHE=128 per replica)
HOT_FRAC = 0.96         # share of traffic from the head


def skewed_stream(log, n: int, seed: int = 11, hot: int = HOT_KEYS,
                  hot_frac: float = HOT_FRAC) -> np.ndarray:
    """Open-loop arrival order: a popularity-weighted navigational head
    of ``hot`` distinct queries carrying ``hot_frac`` of the traffic,
    plus a uniform rare tail.  The head is sized LARGER than one
    replica's result cache but smaller than the fleet's combined
    caches — the regime where routing decides fleet cache efficiency:
    affinity partitions the head across replicas (every repeat hits),
    while blind round-robin makes every cache churn through the full
    head.  The tail exercises depth-balanced miss placement."""
    rng = np.random.default_rng(seed)
    hot_ids = np.argsort(-log.popularity)[:hot]
    p = log.popularity[hot_ids] / log.popularity[hot_ids].sum()
    return np.where(rng.random(n) < hot_frac,
                    rng.choice(hot_ids, size=n, p=p),
                    rng.integers(0, log.n_queries, size=n))


def head_once(log, seed: int = 5, hot: int = HOT_KEYS) -> np.ndarray:
    """Every hot key exactly once, shuffled — the warm pass that places
    cache owners and fills caches deterministically."""
    rng = np.random.default_rng(seed)
    return rng.permutation(np.argsort(-log.popularity)[:hot])


def drive(cluster, stream, pacing_s: float):
    """Submit the stream open-loop (fixed pacing, no backpressure),
    then wait for every ticket.  Returns (results, tickets, wall_s)."""
    t0 = time.time()
    tickets = []
    for qid in stream:
        tickets.append(cluster.submit(int(qid)))
        if pacing_s:
            time.sleep(pacing_s)
    results = [t.result(timeout=300.0) for t in tickets]
    wall = time.time() - t0
    assert all(r is not None for r in results), "dropped tickets"
    return results, tickets, wall


def run_percentiles(results, tickets):
    from repro.cluster import Shed
    from repro.serving.telemetry import pct

    served = [t for t, r in zip(tickets, results) if not isinstance(r, Shed)]
    lat = np.array([t.latency_s for t in served], np.float64)
    return pct(lat, 0.50) * 1e3, pct(lat, 0.99) * 1e3


def config_metrics(cluster, results, tickets, wall) -> dict:
    p50, p99 = run_percentiles(results, tickets)
    stats = cluster.stats()
    cache_hits = sum(r["cache_hits"] for r in stats["replicas"])
    cache_lookups = cache_hits + sum(r["cache_misses"]
                                     for r in stats["replicas"])
    return {
        "wall_s": wall,
        "qps": len(results) / wall,
        "latency_p50_ms": p50,
        "latency_p99_ms": p99,
        "shed_rate": stats["shed_rate"],
        "version_lag_observed_max": stats["version_lag_observed_max"],
        "version_lag_observed_mean": stats["version_lag_observed_mean"],
        "cache_hit_rate": cache_hits / cache_lookups if cache_lookups else 0.0,
        "router": stats["router"],
        "peak_depths": [r["peak_queue_depth"] for r in stats["replicas"]],
    }


def fresh_cluster(sys_, policies, *, replicas, routing, bucket, cache,
                  u_budget=float("inf"), staleness_bound=2, ladder=True,
                  fallbacks=None, prior_shallow_u=None, backend="thread"):
    from repro.cluster import ClusterConfig, ReplicaSet
    from repro.policies import PolicyStore
    from repro.serving import EngineConfig

    store = PolicyStore(staleness_bound=staleness_bound)
    store.publish(dict(policies), fallbacks=fallbacks)
    # Sticky owners should roughly track what the fleet's caches still
    # hold: bound the affinity table to the fleet cache capacity so
    # long-evicted tail keys fall back to depth-balanced routing.
    cluster = ReplicaSet(sys_, store, ClusterConfig(
        n_replicas=replicas, routing=routing, u_inflight_budget=u_budget,
        ladder=ladder, prior_shallow_u=prior_shallow_u, backend=backend,
        affinity_table=max(1, cache) * replicas),
        EngineConfig(min_bucket=bucket, max_bucket=bucket,
                     cache_capacity=cache))
    cluster.warmup()
    return cluster, store


# ---------------------------------------------------------- backend sweep
def _vm_rss_kb(pid):
    """VmRSS of one process from /proc/<pid>/status (kB; None if gone)."""
    try:
        with open(f"/proc/{pid}/status") as fh:
            for line in fh:
                if line.startswith("VmRSS:"):
                    return int(line.split()[1])
    except OSError:
        pass
    return None


def run_backend_sweep(sys_, policies, *, replicas_list, bucket, cache,
                      volume) -> dict:
    """Thread vs process replica backends on the same burst stream.

    Per (backend, replica count): fleet QPS, p50/p99, per-worker VmRSS,
    and — process side — the /proc/<pid>/smaps accounting of the cell's
    index mappings (Rss vs Pss vs Private_Dirty) proving every worker
    serves from ONE shared physical copy of the base generation.

    Honest numbers: ``n_cpus`` is recorded with the results.  On a
    single-core box the process cell pays spawn + ring IPC without any
    hardware parallelism to recoup it — the scaling claim only means
    something when cores >= replicas.  Ends with a FULL bit-parity
    check: the same queries through both backends, identical doc_ids /
    scores / u."""
    import os

    from repro.cluster import Shed
    from repro.launch.cluster import _cell_mapping_stats

    stream = skewed_stream(sys_.log, volume, seed=31)
    warm_stream = head_once(sys_.log)
    out = {"n_cpus": os.cpu_count(), "volume": int(volume), "configs": {}}
    for n_rep in replicas_list:
        for backend in ("thread", "process"):
            cluster, _ = fresh_cluster(
                sys_, policies, replicas=n_rep, routing="queue_aware",
                bucket=bucket, cache=cache, backend=backend)
            with cluster:
                drive(cluster, warm_stream, 0.0)
                res, tk, wall = drive(cluster, stream, 0.0)
                m = config_metrics(cluster, res, tk, wall)
                reps = cluster.stats()["replicas"]
                if backend == "process":
                    pids = [s["worker_pid"] for s in reps]
                    m["worker_restarts"] = [s["n_restarts"] for s in reps]
                    m["worker_rss_kb"] = [_vm_rss_kb(p) for p in pids]
                    m["index_mappings"] = _cell_mapping_stats(
                        pids, cluster.proc_cell_dir)
            out["configs"][f"r{n_rep}_{backend}"] = m
            print(f"cluster_bench.backend.r{n_rep}.{backend}.qps,"
                  f"{m['qps']:.2f}")
            print(f"cluster_bench.backend.r{n_rep}.{backend}.p99_ms,"
                  f"{m['latency_p99_ms']:.2f}")
        t_qps = out["configs"][f"r{n_rep}_thread"]["qps"]
        p_qps = out["configs"][f"r{n_rep}_process"]["qps"]
        ratio = p_qps / t_qps if t_qps else 0.0
        out["configs"][f"r{n_rep}_process"]["qps_vs_thread"] = ratio
        maps = out["configs"][f"r{n_rep}_process"]["index_mappings"]
        if n_rep >= 2:
            # sharing proof needs >= 2 mappers: Pss divides each page
            # by its mapper count, so one physical copy shows up as
            # sum(Pss) ~ sum(Rss)/n.  (private_dirty alone is not
            # usable at n=1 — tmpfs pages are always dirty and count
            # private until a second worker maps them.)
            assert maps["pss_kb_total"] <= 0.75 * maps["rss_kb_total"], maps
        print(f"cluster_bench.backend.r{n_rep}.process_qps_over_thread,"
              f"{ratio:.3f}")
        print(f"cluster_bench.backend.r{n_rep}.index_map_rss_kb,"
              f"{maps['rss_kb_total']} (pss {maps['pss_kb_total']}, "
              f"private_dirty {maps['private_dirty_kb_total']})")

    # FULL bit-parity: identical queries, caches off, both backends —
    # process responses must match the thread reference bit for bit.
    rng = np.random.default_rng(13)
    qids = [int(q) for q in rng.integers(0, sys_.log.n_queries, size=24)]
    got = {}
    for backend in ("thread", "process"):
        cluster, _ = fresh_cluster(
            sys_, policies, replicas=2, routing="queue_aware",
            bucket=bucket, cache=0, backend=backend)
        with cluster:
            got[backend] = cluster.serve(qids)
    for t_resp, p_resp in zip(got["thread"], got["process"]):
        assert not isinstance(t_resp, Shed) and not isinstance(p_resp, Shed)
        np.testing.assert_array_equal(t_resp.doc_ids, p_resp.doc_ids)
        np.testing.assert_array_equal(t_resp.scores, p_resp.scores)
        assert t_resp.u == p_resp.u and \
            t_resp.policy_version == p_resp.policy_version
    out["full_parity_checked"] = len(qids)
    print(f"cluster_bench.backend.full_parity_checked,{len(qids)}")
    return out


# ------------------------------------------------------------- degradation
def _recall(sys_, responses):
    """Mean candidate recall of a response set (None when empty)."""
    from repro.cluster import candidate_recall

    if not responses:
        return None
    ids = np.stack([r.doc_ids for r in responses])
    qs = np.asarray([r.qid for r in responses])
    return float(candidate_recall(ids, sys_.log.judged_ids[qs],
                                  sys_.log.judged_gains[qs]).mean())


def run_degradation(sys_, policies, *, n_rep, bucket, cache, volume,
                    pacing_ms_list=(4.0, 1.0, 0.0)) -> dict:
    """Offered-load sweep at one finite u budget, ladder vs binary."""
    from repro.cluster import ServiceLevel, Shed
    from repro.policies import PolicyStore
    from repro.serving import EngineConfig, ServeEngine
    from repro.serving.telemetry import pct

    fallbacks = sys_.fallback_policies()
    shallow_cap = min(sys_.shallow_u_cap(c) for c in fallbacks)
    stream = skewed_stream(sys_.log, volume, seed=23)
    warm_stream = np.concatenate([head_once(sys_.log),
                                  skewed_stream(sys_.log, volume // 4,
                                                seed=29)])
    # The budget is sized from the LEARNED full-cost estimates after
    # the first warm pass — a few concurrent FULL rollouts per replica
    # saturate it, so a no-pacing burst genuinely pressures the ledger
    # (a static budget either never binds or binds the warm pass too).
    budget = None
    section = {"n_replicas": n_rep, "loads": {}}
    full_parity_checked = 0
    for pacing_ms in pacing_ms_list:
        row = {}
        for mode in ("ladder", "binary"):
            cluster, _ = fresh_cluster(
                sys_, policies, replicas=n_rep, routing="queue_aware",
                bucket=bucket, cache=cache, ladder=(mode == "ladder"),
                fallbacks=fallbacks, prior_shallow_u=float(shallow_cap))
            cluster.start()
            # warm at an open ledger (places owners / fills caches so
            # the CACHED_ONLY rung is real), then tighten the budget
            drive(cluster, warm_stream, pacing_ms / 1e3)
            if budget is None:
                est = cluster.admission.estimator
                est_med = float(np.median(
                    [est.estimate(int(q)) for q in stream]))
                budget = max(4.0 * est_med * n_rep, 8.0 * shallow_cap)
                section["u_inflight_budget"] = budget
                section["est_med_full"] = est_med
            cluster.admission.u_inflight_budget = budget
            res, tk, wall = drive(cluster, stream, pacing_ms / 1e3)
            cluster.stop(drain=True)
            served = [r for r in res if not isinstance(r, Shed)]
            lat = [t.latency_s for t, r in zip(tk, res)
                   if not isinstance(r, Shed)]
            shallow = [r for r in served if r.level == ServiceLevel.SHALLOW]
            row[mode] = {
                "wall_s": wall,
                "qps": len(res) / wall,
                "latency_p50_ms": pct(lat, 0.50) * 1e3,
                "latency_p99_ms": pct(lat, 0.99) * 1e3,
                "served_fraction": len(served) / len(res),
                "mix": {l.name: sum(t.level == l for t in tk)
                        for l in ServiceLevel},
                "recall_served": _recall(sys_, served),
                "recall_shallow": _recall(sys_, shallow),
                "n_shallow": len(shallow),
                "admission": cluster.stats()["admission"],
            }
            if mode == "ladder" and pacing_ms == pacing_ms_list[0]:
                # FULL-level responses must be bit-identical to a plain
                # single-engine serve (the pre-ladder reference path).
                # Checked at the LIGHTEST load, where FULL rollouts
                # dominate — at the burst the watermark throttles FULL
                # grants and the sample could be empty, making the
                # check vacuous.
                sample = [r for r in served
                          if r.level == ServiceLevel.FULL and not r.cached
                          ][:16]
                assert sample, "no non-cached FULL responses to verify"
                ref_store = PolicyStore(staleness_bound=2)
                ref_store.publish(dict(policies))
                ref = ServeEngine(sys_, ref_store, EngineConfig(
                    min_bucket=bucket, max_bucket=bucket, cache_capacity=0))
                for r, rr in zip(sample, ref.serve([r.qid for r in sample])):
                    np.testing.assert_array_equal(r.doc_ids, rr.doc_ids)
                    assert r.u == rr.u, f"FULL u diverged for qid {r.qid}"
                full_parity_checked = len(sample)
        # the ladder never serves less than binary shedding
        assert row["ladder"]["served_fraction"] >= \
            row["binary"]["served_fraction"], row
        key = f"pacing_{pacing_ms:g}ms"
        section["loads"][key] = row
        for mode in ("ladder", "binary"):
            m = row[mode]
            print(f"cluster_bench.degradation.{key}.{mode}."
                  f"served_fraction,{m['served_fraction']:.3f}")
            print(f"cluster_bench.degradation.{key}.{mode}."
                  f"p99_ms,{m['latency_p99_ms']:.2f}")
        print(f"cluster_bench.degradation.{key}.ladder.mix,"
              f"{row['ladder']['mix']}")
    # at the burst (heaviest load) the ladder strictly wins
    burst = section["loads"][f"pacing_{pacing_ms_list[-1]:g}ms"]
    assert burst["ladder"]["served_fraction"] > \
        burst["binary"]["served_fraction"], burst
    section["full_parity_checked"] = full_parity_checked
    return section


def main(fast: bool = False, replicas_list=(1, 2, 4),
         pacing_ms: float = 8.0, repeats: int = 3,
         degradation_only: bool = False,
         backend_sweep_only: bool = False) -> dict:
    from benchmarks.serve_bench import build_system
    from repro.cluster import TrainerConfig, TrainerLoop

    n_docs = 2048 if fast else 4096
    n_queries = 1024 if fast else 2048
    iters = 20 if fast else 60
    volume = 192 if fast else 448
    # Per-replica cache smaller than the hot head (HOT_KEYS): one
    # replica cannot hold the head alone, the fleet (>= 2 replicas)
    # can — routing decides whether it does.
    bucket, cache = 8, 128
    pacing_s = pacing_ms / 1e3

    sys_, policies = build_system(n_docs, n_queries, iters)
    # One fresh draw per timed run: the head recurs across draws
    # (caches/affinity stay warm for it), the tail varies.
    streams = [skewed_stream(sys_.log, volume, seed=11 + i)
               for i in range(repeats)]
    stream = streams[0]
    # Warm = every hot key once (places owners/fills caches), then a
    # paced mixed prefix.
    warm_stream = np.concatenate([head_once(sys_.log),
                                  skewed_stream(sys_.log, volume // 4,
                                                seed=7)])

    out = {"volume": volume, "pacing_ms": pacing_ms, "repeats": repeats,
           "configs": {}}

    if backend_sweep_only:
        out["backend"] = run_backend_sweep(
            sys_, policies, replicas_list=replicas_list, bucket=bucket,
            cache=cache, volume=volume)
        from benchmarks._results import record
        record("cluster_bench_backend",
               config={"fast": fast, "n_docs": n_docs,
                       "n_queries": n_queries,
                       "replicas": list(replicas_list), "volume": volume,
                       "bucket": bucket},
               metrics=out["backend"])
        return out

    if degradation_only:
        out["degradation"] = run_degradation(
            sys_, policies, n_rep=max(replicas_list), bucket=bucket,
            cache=cache, volume=volume,
            pacing_ms_list=(4.0, 0.0) if fast else (4.0, 1.0, 0.0))
        from benchmarks._results import record
        record("cluster_bench_degradation",
               config={"fast": fast, "n_docs": n_docs,
                       "n_queries": n_queries,
                       "replicas": max(replicas_list), "volume": volume,
                       "bucket": bucket},
               metrics=out["degradation"])
        return out

    # ------------------------------------------- 1. routing x replicas
    # p99 on an oversubscribed CPU box is noisy, so the routers are
    # compared PAIRED: both clusters stay up, each fresh stream is
    # driven through one then the other (interleaved, so slow machine
    # drift hits both equally), and the MEDIAN per-run p99 is the
    # headline.  The warm pass uses the same pacing as the timed runs
    # (a burst warm would place cache owners under unrepresentative
    # queue depths and lock that skew in).
    routings = ("queue_aware", "round_robin")
    for n_rep in replicas_list:
        clusters = {routing: fresh_cluster(sys_, policies, replicas=n_rep,
                                           routing=routing, bucket=bucket,
                                           cache=cache)[0]
                    for routing in routings}
        p99s = {routing: [] for routing in routings}
        last = {}
        for routing in routings:
            clusters[routing].start()
            drive(clusters[routing], warm_stream, pacing_s)
        for i, s in enumerate(streams):
            # alternate who goes first so slow machine drift and
            # warmer-second effects cancel across the pairing
            order = routings if i % 2 == 0 else routings[::-1]
            for routing in order:
                res, tk, wall = drive(clusters[routing], s, pacing_s)
                p99s[routing].append(run_percentiles(res, tk)[1])
                last[routing] = (res, tk, wall)
        for routing in routings:
            clusters[routing].stop(drain=True)
            m = config_metrics(clusters[routing], *last[routing])
            m["latency_p99_ms"] = float(np.median(p99s[routing]))
            m["latency_p99_ms_runs"] = p99s[routing]
            out["configs"][f"r{n_rep}_{routing}"] = m
            print(f"cluster_bench.r{n_rep}.{routing}."
                  f"p99_ms,{m['latency_p99_ms']:.2f}")
            print(f"cluster_bench.r{n_rep}.{routing}.qps,{m['qps']:.2f}")

    for n_rep in replicas_list:
        qa = out["configs"][f"r{n_rep}_queue_aware"]["latency_p99_ms"]
        rr = out["configs"][f"r{n_rep}_round_robin"]["latency_p99_ms"]
        out["configs"][f"r{n_rep}_queue_aware"]["p99_vs_round_robin"] = \
            qa / rr if rr else 1.0
        print(f"cluster_bench.r{n_rep}.p99_queue_aware_over_round_robin,"
              f"{qa / rr if rr else 1.0:.3f}")

    # ------------------------------------------------ 2. online serving
    n_rep = max(replicas_list)
    cluster, store = fresh_cluster(sys_, policies, replicas=n_rep,
                                   routing="queue_aware", bucket=bucket,
                                   cache=cache)
    trainer = TrainerLoop(sys_, store, cfg=TrainerConfig(
        iters=4, publish_every=2, batch=16, probe_queries=8, gate=False,
        publish_initial=False))
    with cluster:
        trainer.start()
        res, tk, wall = drive(cluster, stream, pacing_s)
        trainer.join()
        res2, tk2, wall2 = drive(cluster, stream[: volume // 2], pacing_s)
    m = config_metrics(cluster, res + res2, tk + tk2, wall + wall2)
    m["versions_published"] = trainer.versions_published
    out["online"] = m
    print(f"cluster_bench.online.version_lag_max,"
          f"{m['version_lag_observed_max']}")
    print(f"cluster_bench.online.versions,{len(trainer.versions_published)}")

    # --------------------------------------------------- 3. admission
    tight = sys_.cfg.u_budget * 4 * n_rep
    cluster, _ = fresh_cluster(sys_, policies, replicas=n_rep,
                               routing="queue_aware", bucket=bucket,
                               cache=0, u_budget=tight)
    with cluster:
        res, tk, wall = drive(cluster, stream, 0.0)   # burst: no pacing
    m = config_metrics(cluster, res, tk, wall)
    m["u_inflight_budget"] = tight
    out["admission"] = m
    print(f"cluster_bench.admission.shed_rate,{m['shed_rate']:.3f}")

    # ------------------------------------------------ 4. degradation
    out["degradation"] = run_degradation(
        sys_, policies, n_rep=n_rep, bucket=bucket, cache=cache,
        volume=volume, pacing_ms_list=(4.0, 0.0) if fast else (4.0, 1.0, 0.0))

    from benchmarks._results import record
    record("cluster_bench",
           config={"fast": fast, "n_docs": n_docs, "n_queries": n_queries,
                   "replicas": list(replicas_list), "volume": volume,
                   "pacing_ms": pacing_ms, "bucket": bucket},
           metrics=out)
    return out


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--fast", action="store_true")
    ap.add_argument("--replicas", default="1,2,4",
                    help="comma-separated replica counts to sweep")
    ap.add_argument("--pacing-ms", type=float, default=8.0)
    ap.add_argument("--repeats", type=int, default=3,
                    help="timed runs per config (median p99 reported)")
    ap.add_argument("--degradation-only", action="store_true",
                    help="run only the ladder-vs-binary degradation sweep "
                         "(make degrade-bench)")
    ap.add_argument("--backend-sweep", action="store_true",
                    help="run only the thread-vs-process replica backend "
                         "sweep (make proc-bench)")
    a = ap.parse_args()
    main(fast=a.fast,
         replicas_list=tuple(int(x) for x in a.replicas.split(",")),
         pacing_ms=a.pacing_ms, repeats=a.repeats,
         degradation_only=a.degradation_only,
         backend_sweep_only=a.backend_sweep)
