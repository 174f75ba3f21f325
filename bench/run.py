"""Run one benchmark cell once.

    python3 -m bench.run --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (counted in ``setup_s``): stamp the device and refuse anything
but a TPU with the cell's chips; make the served tables from the seed in
one jitted call; let the program build the corpus, index and query log
from the seed; compile the cell's bucket executables (from the
persistent compile cache after the first run) and serve one micro-batch
of each bucket size, so nothing compiles in the window.  Then the cell's
traffic mix runs for ``--seconds`` through ``ReplicaSet.submit`` and is
drained.  After the window: peak device memory is read, every answer is
compared with the plain reference (``bench/check.py``), and the last
line of standard output is one JSON object with ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown``
when traced), and last ``check``: each number compared with its limit.
The same numbers are the last lines of standard error.

``--trace 1`` runs the same window under the JAX profiler and the
program's tracer, and reports the cell's per-layer metrics instead of
its end-to-end ones.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from typing import Callable, List, Optional  # noqa: E402

from .spec import Cell, load_cell, load_readers  # noqa: E402


def log(phase: str, **fields) -> None:
    print(json.dumps({"phase": phase, **fields}, default=str), flush=True)


class Harness:
    """The system under test, set up for one cell and seed, with the
    replica set started.  ``close()`` stops it."""

    def __init__(self, cell: Cell, seed: int, trace: bool, devices):
        import jax

        from . import loadgen, sut
        from .device import CompileCounter, memory_peak_bytes
        from .weights import make_weights

        from repro.compile_cache import enable_compile_cache
        from repro.obs import NULL_TRACER, Tracer

        self.cell, self.seed, self.trace, self.devices = cell, seed, trace, devices
        cfg = cell.config
        log("compile_cache", dir=enable_compile_cache())
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        # The configuration states the L1 ranker's precision; JAX's
        # matmul precision is the program's only switch for it.
        jax.config.update("jax_default_matmul_precision",
                          cfg["l1_matmul_precision"])
        self.compiles = CompileCounter()
        t = time.perf_counter()
        self.weights = make_weights(seed, cfg)
        t_weights = time.perf_counter() - t
        t = time.perf_counter()
        self.system, store = sut.build_system(cfg, seed, self.weights)
        t_build = time.perf_counter() - t
        self.tracer = Tracer() if trace else NULL_TRACER
        self.rs = sut.replica_set(self.system, store, cfg, self.tracer)
        t = time.perf_counter()
        n_exe = self.rs.warmup()
        t_compile = time.perf_counter() - t
        self.rs.start()
        t = time.perf_counter()
        self.warm = sut.warmup_queries(self.system.log, cfg, seed)
        for group in self.warm:
            res = self.rs.serve_many(group, timeout_s=600.0)
            if not all(sut.is_response(r) for r in res):
                raise RuntimeError("a warm-up query was not served")
        t_warm = time.perf_counter() - t
        self.used = [q for g in self.warm for q in g]
        self.done_q = loadgen.hook_completions(self.rs)
        log("setup", weights_s=t_weights, build_s=t_build,
            compile_s=t_compile, executables=n_exe,
            warm_buckets=[len(g) for g in self.warm], warm_s=t_warm,
            n_docs=self.system.index.n_docs,
            n_blocks=self.system.env_cfg.n_blocks,
            log_queries=self.system.log.n_queries,
            memory_peak_bytes=memory_peak_bytes(devices))

    def measure(self, traffic: dict, seconds: float,
                on_start: Callable[[float], None] = lambda t0: None):
        """Offer ``traffic`` for ``seconds`` and drain it.  Returns
        ``(window, telemetry rows of its micro-batches, trace directory
        or None)``."""
        import jax
        import numpy as np

        from . import loadgen, sut
        from .device import HostCpu

        log_ = self.system.log
        seq = loadgen.plan_queries(log_.terms, log_.category,
                                   log_.popularity, traffic, self.seed,
                                   exclude=self.used)
        need = int(self.cell.config.get("served_log", {}).get("min_unique", 0))
        if len(seq) < need:
            raise ValueError(
                f"the query log plans {len(seq)} unique queries, under the "
                f"{need} its configuration's served_log.min_unique asks for")
        rows = [r.engine.telemetry.batches for r in self.rs.replicas]
        n0 = [len(r) for r in rows]
        state = {}

        def start(t0: float) -> None:
            state["compiles0"] = self.compiles.count
            state["cpu"] = HostCpu()
            if self.trace:
                with jax.profiler.TraceAnnotation("bench.window_start"):
                    pass
            on_start(t0)

        trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if self.trace else None
        if self.trace:
            jax.profiler.start_trace(trace_dir)
        try:
            window = loadgen.drive(self.rs.submit, self.done_q, traffic, seq,
                                   seconds, self.seed, sut.is_response,
                                   on_start=start,
                                   submit_many=self.rs.submit_many)
        finally:
            if self.trace:
                jax.profiler.stop_trace()
        in_window = self.compiles.count - state["compiles0"]
        self.used += [r.qid for r in window.records]
        batches = [row for r, k in zip(rows, n0) for row in list(r)[k:]]
        lat = window.lateness_s()
        by_bucket = {}
        for row in batches:
            by_bucket[row["bucket"]] = by_bucket.get(row["bucket"], 0) + 1
        log("window", attempted=len(window.records), failed=window.failed,
            cached=sum(bool(getattr(r.result, "cached", False))
                       for r in window.completed),
            microbatches_by_bucket=by_bucket,
            xla_compiles_in_window=in_window,
            generator_lateness_s={"max": float(np.max(lat, initial=0.0)),
                                  "p95": loadgen.nearest_rank(lat, 0.95)},
            log_queries_per_sent=log_.n_queries / max(len(window.records), 1),
            unique_supply=len(seq),
            host_cpu=state["cpu"].reading())
        return window, batches, trace_dir

    def answers(self, records, l1_precision: str = "float64") -> List:
        """The plain reference's answer to each record's query."""
        import numpy as np

        from . import reference

        log_ = self.system.log
        terms = (np.concatenate([log_.terms[r.qid] for r in records])
                 if records else np.zeros(0, np.int64))
        corpus = self.system.corpus
        postings = reference.Postings(corpus.field_terms, corpus.static_rank,
                                      terms[terms >= 0])
        return [reference.answer(postings, log_.terms[r.qid],
                                 int(log_.category[r.qid]), self.weights,
                                 self.cell.config, l1_precision)
                for r in records]

    def close(self) -> None:
        self.rs.stop(drain=False)


class RunData:
    """What a per-layer reader may read from one run."""

    def __init__(self, cell: Cell, window, batches, spans, trace,
                 device_kind: str):
        self.cell = cell
        self.config = cell.config
        self.window = window          # loadgen.Window
        self.batches = batches        # engine telemetry rows of the window
        self.spans = spans            # tracer entries (traced runs)
        self.trace = trace            # dict from reduce_trace (traced runs)
        self.device_kind = device_kind


def reduce_trace(log_dir: str, t0: float, t_end: float) -> dict:
    """Device events of the traced window [t0, t_end] (host clock)."""
    from . import trace_reduce as tr

    events = tr.load_xplane(tr.find_xplane(log_dir))
    m = tr.marker_ns(events)
    lo, hi = m, m + (t_end - t0) * 1e9
    planes = tr.device_planes(events)
    per_chip = [tr.busy_ns(tr.device_ops(events, p), lo, hi) for p in planes]
    return {"ops": tr.device_ops(events), "modules": tr.device_modules(events),
            "lo": lo, "hi": hi, "to_ns": lambda s: m + (s - t0) * 1e9,
            "busy_s": (sum(per_chip) / len(per_chip) * 1e-9 if per_chip
                       else 0.0),
            "window_s": (hi - lo) * 1e-9}


def breakdown(trace: dict, spans) -> dict:
    from . import trace_reduce as tr

    lo, hi = trace["lo"], trace["hi"]
    gaps = tr.idle_gaps(trace["ops"], lo, hi)
    return {"device_ops": tr.top(tr.time_by_name(trace["ops"], lo, hi,
                                                 short=True)),
            "idle_gaps": tr.top(tr.label_gaps(gaps, spans or [],
                                              trace["to_ns"]))}


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             stamp: dict, devices, t_process: float = T_PROCESS,
             out: Callable[[str], None] = print,
             err: Optional[Callable[[str], None]] = None) -> dict:
    """One run of ``cell``; returns the result object it printed."""
    from . import check, loadgen
    from .device import memory_peak_bytes

    err = err or (lambda s: print(s, file=sys.stderr, flush=True))
    cfg = cell.config
    log("cell", workload=cell.name, config=cell.config_name,
        traffic=cell.traffic_name, seed=seed, seconds=seconds, trace=trace,
        device=stamp)
    setup = {}
    h = Harness(cell, seed, trace, devices)
    try:
        window, batches, trace_dir = h.measure(
            cell.traffic, seconds,
            on_start=lambda t0: setup.setdefault("s", t0 - t_process))
    finally:
        h.close()
    peak = memory_peak_bytes(devices)
    log("memory", memory_peak_bytes=peak)
    spans = h.tracer.log.snapshot() if trace else None

    # The plain reference, after the window and the memory read.
    t = time.perf_counter()
    done = window.completed
    numbers = check.compare(
        zip((r.result for r in done), h.answers(done)),
        int(cfg["widths"]["keep"]))
    limits = cfg["correct"]["limits"]
    correct, lines = check.verdict(numbers, limits, window.failed)
    log("reference", seconds=time.perf_counter() - t,
        compared=numbers["n_compared"])

    device = dict(stamp, memory_peak_bytes=peak)
    result = {"correct": bool(correct), "attempted": len(window.records),
              "failed": int(window.failed)}
    if trace:
        t_end = max([r.done for r in window.records if r.done is not None],
                    default=window.t_close)
        tr_data = reduce_trace(trace_dir, window.t0, t_end)
        shutil.rmtree(trace_dir, ignore_errors=True)
        device.update(busy_s=tr_data["busy_s"], window_s=tr_data["window_s"])
        run = RunData(cell, window, batches, spans, tr_data, stamp["kind"])
        readers = load_readers(cell.per_layer)
        metrics = {}
        for m in cell.per_layer:
            value = readers[m["name"]](run)
            if value is not None:
                metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
        result.update(metrics=metrics, device=device,
                      breakdown=breakdown(tr_data, spans))
    else:
        e2e = {"setup_s": lambda: setup["s"], "qps": window.qps,
               "p95_ms": lambda: 1e3 * loadgen.nearest_rank(
                   window.latencies_s(), 0.95)}
        result.update(metrics={m["name"]: {"value": float(e2e[m["name"]]()),
                                           "unit": m["unit"]}
                               for m in cell.end_to_end},
                      device=device)
    result["check"] = {name: {"value": numbers[name], "limit": limits[name]}
                       for name in check.NUMBERS}
    result["check"]["unanswered"] = {"value": window.failed, "limit": 0}
    for line in lines:
        err(line)
    out(json.dumps(result))
    return result


def main(argv: Optional[list] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = load_cell(args.workload)
    from .device import NoAccelerator, device_stamp

    try:
        stamp = device_stamp(cell.chips)
    except NoAccelerator as e:
        print(f"bench.run: {e}", file=sys.stderr)
        return 2
    import jax

    run_cell(cell, args.seed, args.seconds, bool(args.trace), stamp,
             jax.devices()[:cell.chips])
    return 0


if __name__ == "__main__":
    sys.exit(main())
