#!/usr/bin/env python3
"""Smoke test of the learned match-planning serve path on a TPU.

    python chip_smoke.py              # one chip: the full serve path
    python chip_smoke.py --chips 4    # four chips: the document-sharded cell

One chip: builds the ``websearch-rl`` system at its published widths
(block_docs=4096, p_bins=10000, u_budget=65536) over one index shard of
its production mesh — 256 blocks, 1,048,576 documents — through
``repro.launch.serve.build_system`` (index, L1 fit, state bins, policy
training), then serves the same query batches through two
``ServeEngine``s, one on the ``pallas_block_scan`` backend and one on
the ``xla`` reference, and checks that every response is bit-identical,
that nothing compiles after ``warmup()``, and that the served
executable holds the compiled kernel (``tpu_custom_call``).

Four chips: one 256-block shard per chip (4,194,304 documents), served
by the ``serve_websearch`` cell (launch/steps.py) on a 1x4 (data x
model) mesh and compared with ``ShardedExecutor(n_shards=4)`` on one
device: merged candidates, u and candidate counts must be equal.

Timings, compile seconds, memory and the cuts of scale are printed on
the lines before the last.  The last line is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every check held.
Without a TPU the script exits non-zero before doing any work.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

BLOCKS_PER_CHIP = 256      # websearch-rl's 4096 blocks over a 16-way model axis
BUCKET = 16                # 256 queries over a 16-way data axis
N_BATCHES = 3


def log(name: str, **fields) -> None:
    print(json.dumps({"phase": name, **fields}, default=str), flush=True)


def check(cond: bool, what: str) -> None:
    if not cond:
        raise RuntimeError(f"check failed: {what}")


def device_stamp(n_chips: int) -> dict:
    import jax

    devs = jax.devices()
    stamp = {"platform": devs[0].platform, "kind": devs[0].device_kind,
             "count": len(devs)}
    log("device", **stamp)
    if stamp["platform"] != "tpu":
        raise SystemExit(f"no TPU: jax.devices()[0] is {stamp['platform']!r}")
    if len(devs) < n_chips:
        raise SystemExit(f"need {n_chips} chips, JAX reports {len(devs)}")
    return stamp


def _compile_counter():
    """Counts XLA backend compiles from JAX's monitoring events."""
    import jax

    count = [0]

    def listener(event, duration_secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            count[0] += 1

    jax.monitoring.register_event_duration_secs_listener(listener)
    return count


def _memory(device) -> dict:
    """Device memory now, at its peak so far, and its limit (bytes)."""
    stats = device.memory_stats() or {}
    return {k: stats.get(k, "not reported")
            for k in ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")}


def _bit_equal(a, b) -> bool:
    import numpy as np

    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and a.dtype == b.dtype and a.tobytes() == b.tobytes()


def serve_one_chip(seed: int, n_blocks: int = BLOCKS_PER_CHIP,
                   n_queries: int = 512) -> None:
    import jax
    import numpy as np

    from repro.data.querylog import CAT1, CAT2
    from repro.launch.serve import build_system
    from repro.serving import EngineConfig, ServeEngine

    n_docs = n_blocks * 4096
    log("reduced", n_docs=n_docs, n_blocks=n_blocks,
        cuts={"n_blocks": "4096 -> one shard of the 16-way model axis",
              "n_queries": f"QueryLogConfig default 2000 -> {n_queries}",
              "fit_l1": "64 judged queries in batches of 8, 50 Adam steps",
              "fit_state_bins": "32 baseline queries per category, batches of 8",
              "train_policy_store": "2 iterations of 16 queries per category",
              "query_batch": f"256 -> {BUCKET} (one data-axis shard)"})
    sys_, store, secs = build_system(
        arch="websearch-rl", n_docs=n_docs, n_queries=n_queries, iters=2,
        train_batch=16, l1_queries=64, bins_queries=32, l1_steps=50,
        fit_batch=8, seed=seed)
    log("build", seconds=secs, n_docs=sys_.index.n_docs,
        n_blocks=sys_.env_cfg.n_blocks, block_docs=sys_.cfg.block_docs,
        p_bins=sys_.cfg.p_bins, u_budget=sys_.cfg.u_budget,
        memory=_memory(jax.devices()[0]))
    check(sys_.index.n_docs == n_docs and sys_.env_cfg.n_blocks == n_blocks,
          "index holds the requested documents and blocks")

    # Unique queries, one category per micro-batch: every lane executes.
    rng = np.random.default_rng(seed)
    batches = []
    for i in range(N_BATCHES):
        cat = (CAT1, CAT2)[i % 2]
        pool = np.setdiff1d(np.where(sys_.log.category == cat)[0],
                            np.concatenate(batches) if batches else [])
        batches.append(rng.choice(pool, size=BUCKET, replace=False))

    compiles = _compile_counter()
    results, kernel = {}, {}
    for backend in ("pallas_block_scan", "xla"):
        engine = ServeEngine(sys_, store, EngineConfig(
            min_bucket=BUCKET, max_bucket=BUCKET, backend=backend))
        t0 = time.perf_counter()
        n_exe = engine.warmup()
        compile_s = time.perf_counter() - t0
        # The cached executable: compiled_for compiles nothing here.
        exe = engine.executor.compiled_for(BUCKET, store.snapshot().policies[CAT1])
        kernel[backend] = "tpu_custom_call" in exe.as_text()
        xla_before = compiles[0]
        responses, batch_s = [], []
        for qids in batches:
            t0 = time.perf_counter()
            responses += engine.serve_many(qids)
            batch_s.append(time.perf_counter() - t0)
        log("serve", backend=backend, warmup_executables=n_exe,
            warmup_compile_s=compile_s, batch_s=batch_s,
            engine_compiles_after_warmup=engine.compile_count - n_exe,
            xla_compiles_while_serving=compiles[0] - xla_before,
            tpu_custom_call=kernel[backend],
            mean_u=float(np.mean([r.u for r in responses])),
            mean_cand_cnt=float(np.mean([r.cand_cnt for r in responses])),
            memory=_memory(jax.devices()[0]))
        check(engine.compile_count == n_exe,
              f"{backend}: no executable compiled after warmup")
        check(all(not r.cached for r in responses),
              f"{backend}: every response came from the device")
        results[backend] = responses
    check(kernel["pallas_block_scan"],
          "pallas_block_scan executable holds the compiled kernel")

    pal, ref = results["pallas_block_scan"], results["xla"]
    check(len(pal) == len(ref) == N_BATCHES * BUCKET, "every query answered")
    for a, b in zip(pal, ref):
        check(a.qid == b.qid, "responses in submission order")
        for field in ("doc_ids", "scores", "u", "cand_cnt"):
            check(_bit_equal(getattr(a, field), getattr(b, field)),
                  f"qid {a.qid}: {field} bit-identical across backends")
    log("parity", queries=len(pal), fields=["doc_ids", "scores", "u", "cand_cnt"],
        bit_identical=True, memory=_memory(jax.devices()[0]))


def serve_four_chips(seed: int, blocks_per_chip: int = BLOCKS_PER_CHIP,
                     n_queries: int = 128) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding

    from repro.configs.websearch_rl import WebSearchCfg
    from repro.data.querylog import QueryLogConfig
    from repro.index.corpus import CorpusConfig
    from repro.launch.mesh import make_mesh
    from repro.launch.serve import arch_widths
    from repro.launch.steps import build_cell
    from repro.policies import TabularQPolicy
    from repro.serving.executor import ShardedExecutor
    from repro.system import RetrievalSystem, SystemConfig

    n_chips = 4
    n_blocks = n_chips * blocks_per_chip
    n_docs = n_blocks * 4096
    log("reduced", n_docs=n_docs, n_blocks=n_blocks,
        cuts={"n_blocks": f"4096 -> {n_chips} shards of the 16-way model axis",
              "n_queries": f"QueryLogConfig default 2000 -> {n_queries}",
              "l1": "random L1 weights from the seed (no fit)",
              "policy": "random Q-table from the seed (no training)",
              "fit_state_bins": "8 baseline queries per category",
              "query_batch": f"256 -> {BUCKET}"})
    t0 = time.perf_counter()
    sys_ = RetrievalSystem(SystemConfig(
        corpus=CorpusConfig(n_docs=n_docs, vocab_size=2048, seed=seed),
        querylog=QueryLogConfig(n_queries=n_queries, seed=seed),
        seed=seed, **arch_widths("websearch-rl")))
    build_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    # Inputs are built 2 queries at a time: the host-side occupancy and
    # eager L1 scoring of a batch over 4M documents all sit on device 0.
    sys_.fit_state_bins(n_queries=8, batch=2)
    bins_s = time.perf_counter() - t0
    log("build", seconds={"build_index": build_s, "fit_state_bins": bins_s},
        n_docs=sys_.index.n_docs, n_blocks=sys_.env_cfg.n_blocks,
        memory=_memory(jax.devices()[0]))

    rng = np.random.default_rng(seed)
    q = np.abs(rng.normal(0, 0.1, size=(sys_.qcfg.p, sys_.qcfg.n_actions)))
    q[:, :-2] += 1.0           # prefer match rules over reset/stop
    policy = TabularQPolicy(jnp.asarray(q, jnp.float32))
    qids = rng.choice(sys_.log.n_queries, size=BUCKET, replace=False)
    occ, scores, tp = (jnp.concatenate(parts) for parts in zip(
        *(sys_.batch_inputs(qids[i:i + 2]) for i in range(0, BUCKET, 2))))

    mesh = make_mesh((1, n_chips), ("data", "model"),
                     devices=jax.devices()[:n_chips])
    wcfg = WebSearchCfg(n_blocks=n_blocks, block_docs=sys_.cfg.block_docs,
                        backend="pallas_block_scan")
    cell = build_cell("websearch-rl", "serve_queries", mesh=mesh,
                      cfg_override=wcfg)
    args = jax.device_put((policy.q, sys_.bins, occ, scores, tp),
                          cell.in_shardings)
    occ_devs = {s.device for s in args[2].addressable_shards}
    per_dev = [_memory(d)["bytes_in_use"] for d in mesh.devices.flat]
    log("placement", occ_sharding=str(args[2].sharding),
        occ_shard_shape=list(args[2].addressable_shards[0].data.shape),
        occ_devices=sorted(d.id for d in occ_devs), bytes_in_use=per_dev)
    check(len(occ_devs) == n_chips, "occupancy shards on four devices")
    check(isinstance(args[2].sharding, NamedSharding), "occupancy is mesh-sharded")

    step = jax.jit(cell.fn, in_shardings=cell.in_shardings)
    t0 = time.perf_counter()
    compiled = step.lower(*args).compile()
    compile_s = time.perf_counter() - t0
    check("tpu_custom_call" in compiled.as_text(), "cell holds the compiled kernel")
    t0 = time.perf_counter()
    merged, u, cnt = jax.block_until_ready(compiled(*args))
    cell_s = time.perf_counter() - t0

    ex = ShardedExecutor(sys_, n_shards=n_chips, backend="xla")
    ref_fn = jax.jit(ex.merged_candidates)
    t0 = time.perf_counter()
    r_merged, r_u, r_cnt = jax.block_until_ready(
        ref_fn(sys_.bins, policy, occ, scores, tp))
    ref_s = time.perf_counter() - t0
    per_dev = [_memory(d)["peak_bytes_in_use"] for d in mesh.devices.flat]
    log("sharded_serve", cell_compile_s=compile_s, cell_s=cell_s,
        reference_s_incl_compile=ref_s, mean_u=float(np.mean(np.asarray(u))),
        mean_cand_cnt=float(np.mean(np.asarray(cnt))),
        peak_bytes_in_use=per_dev)
    for name, a, b in (("merged candidates", merged, r_merged), ("u", u, r_u),
                       ("candidate counts", cnt, r_cnt)):
        check(_bit_equal(a, b), f"{name} equal to ShardedExecutor(n_shards=4)")
    log("parity", reference="ShardedExecutor(n_shards=4, backend='xla') on one device",
        equal=["merged candidates", "u", "candidate counts"])


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    stamp = device_stamp(args.chips)
    from repro.compile_cache import enable_compile_cache

    log("compile_cache", dir=enable_compile_cache())
    t0 = time.perf_counter()
    if args.chips == 4:
        serve_four_chips(args.seed)
    else:
        serve_one_chip(args.seed)
    log("done", seconds=time.perf_counter() - t0)
    print(json.dumps({"ok": True, "device": stamp}))


if __name__ == "__main__":
    main()
