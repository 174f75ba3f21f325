"""The trace reduction on synthetic traces, and on one recorded on the
CPU (where only the host planes exist)."""
import numpy as np
import pytest

from bench import trace_reduce as tr
from bench.trace_reduce import Event

DEV = "/device:TPU:0"


def ev(name, start, dur, line=tr.OPS_LINE, plane=DEV, stats=None):
    return Event(plane, line, name, float(start), float(dur), stats)


def test_union_busy_and_gaps():
    events = [ev("a", 0, 10), ev("b", 5, 10), ev("c", 30, 5), ev("d", 50, 100)]
    assert tr.union([(0, 10), (5, 15), (30, 35)]) == [(0, 15), (30, 35)]
    assert tr.busy_ns(events, 0, 60) == 15 + 5 + 10
    assert tr.idle_gaps(events, 0, 60) == [(15, 30), (35, 50)]
    assert tr.idle_gaps([], 0, 7) == [(0, 7)]
    assert tr.time_by_name(events, 0, 60) == {"a": 10, "b": 10, "c": 5, "d": 10}


def test_device_lines_and_marker():
    events = [ev("fusion", 0, 5), ev("jit__serve_fn(3)", 0, 8, tr.MODULES_LINE),
              ev("jit_mul(1)", 10, 2, tr.MODULES_LINE),
              ev("host op", 0, 100, "python", "/host:CPU"),
              ev(tr.WINDOW_MARKER, 42, 1, "python", "/host:CPU"),
              ev("fusion", 0, 3, plane="/device:TPU:1")]
    assert tr.device_planes(events) == [DEV, "/device:TPU:1"]
    assert [e.name for e in tr.device_ops(events, DEV)] == ["fusion"]
    assert len(tr.device_ops(events)) == 2
    assert len(tr.device_modules(events)) == 2
    assert tr.marker_ns(events) == 42
    with pytest.raises(ValueError):
        tr.marker_ns(events[:1])
    trace = {"modules": tr.device_modules(events), "lo": 0, "hi": 100}
    assert tr.per_batch_ms(trace, 2, serve=True) == pytest.approx(4e-6)
    assert tr.per_batch_ms(trace, 2, serve=False) == pytest.approx(1e-6)
    assert tr.per_batch_ms(None, 2, serve=True) is None


def test_gaps_labelled_by_innermost_open_span():
    spans = [{"kind": "span", "name": "microbatch", "t0": 0.0, "t1": 10.0},
             {"kind": "span", "name": "batch_inputs", "t0": 1.0, "t1": 4.0},
             {"kind": "span", "name": "queue", "t0": 0.0, "t1": 20.0},
             {"kind": "instant", "name": "route", "t0": 2.0, "t1": 2.0}]
    gaps = [(2.0, 3.0), (5.0, 7.0), (12.0, 13.0)]
    got = tr.label_gaps(gaps, spans, lambda s: s)
    assert got == {"batch_inputs": 1.0, "microbatch": 2.0, "no span": 1.0}
    assert tr.top(got, n=2, scale=1.0) == [["microbatch", 2.0],
                                          ["batch_inputs", 1.0]]


def test_recorded_cpu_trace_has_the_marker(tmp_path):
    import jax
    import jax.numpy as jnp

    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(tr.WINDOW_MARKER):
            pass
        jnp.ones((8, 8)).sum().block_until_ready()
    finally:
        jax.profiler.stop_trace()
    events = tr.load_xplane(tr.find_xplane(str(tmp_path)))
    assert np.isfinite(tr.marker_ns(events))
    assert tr.device_planes(events) == []          # the CPU has none
