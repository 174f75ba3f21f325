"""The tracer's spans on the profiler's clock: one traced engine
micro-batch of the tiny system under ``jax.profiler`` (CPU), where every
live thread-track span also enters a ``TraceAnnotation``; the spans
inside the input build and the executor, and their ``bytes`` arg; spans
ended on an error path; and the names the benchmark's trace readers key
on."""
import contextlib
import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.querylog import CAT1, CAT2
from repro.index.live import StaleIndexEpochError
from repro.obs import NULL_SPAN, NULL_TRACER, Tracer
from repro.policies import TabularQPolicy
from repro.serving import EngineConfig, ServeEngine

BUCKET = 4
MARKER = "test.window_start"
# Parent of each span a traced micro-batch opens live on its thread.
PARENT = {"microbatch": None, "batch_inputs": "microbatch",
          "occupancy": "batch_inputs", "scatter": "occupancy",
          "pack": "occupancy", "h2d": "batch_inputs",
          "l1_dispatch": "batch_inputs", "execute": "microbatch",
          "dispatch": "execute", "device_wait": "execute",
          "d2h": "execute"}


def _policy(sys_):
    rng = np.random.default_rng(0)
    q = np.abs(rng.normal(0, 0.1, (sys_.qcfg.p, sys_.qcfg.n_actions)))
    q[:, :-2] += 1.0                       # prefer match rules
    return TabularQPolicy(jnp.asarray(q, jnp.float32))


def _engine(sys_, tracer):
    pol = _policy(sys_)
    return ServeEngine(sys_, {c: pol for c in (CAT1, CAT2)}, EngineConfig(
        min_bucket=BUCKET, max_bucket=BUCKET, cache_capacity=0),
        tracer=tracer)


def _host_events(log_dir):
    """(name, start ns) of every event on a host plane of the trace."""
    import glob

    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    return [(ev.name, float(ev.start_ns))
            for plane in ProfileData.from_file(path).planes
            if not plane.name.startswith("/device:")
            for line in plane.lines for ev in line.events]


@contextlib.contextmanager
def _profiled(log_dir):
    """Profile the block; yields the host clock read as ``MARKER`` opens."""
    t = {}
    jax.profiler.start_trace(str(log_dir))
    try:
        with jax.profiler.TraceAnnotation(MARKER):
            t["marker"] = time.perf_counter()
        yield t
    finally:
        jax.profiler.stop_trace()


@pytest.fixture(scope="module")
def traced(tiny_system, tmp_path_factory):
    """One micro-batch served under the profiler with the tracer on,
    after a warm-up batch outside it (compiles stay out of the trace)."""
    sys_ = tiny_system
    qids = np.where(sys_.log.category == CAT1)[0]
    engine = _engine(sys_, Tracer())
    engine.serve(qids[:BUCKET])
    n_warm = len(engine.tracer.log)
    log_dir = tmp_path_factory.mktemp("profile")
    with _profiled(log_dir) as t:
        engine.serve(qids[BUCKET:2 * BUCKET])
    # The thread's spans; each ticket's track holds its own view.
    spans = [s for s in engine.tracer.log.snapshot()[n_warm:]
             if s["kind"] == "span" and not s["track"].startswith("ticket")]
    return {"engine": engine, "spans": spans, "t_marker": t["marker"],
            "events": _host_events(log_dir),
            "qids": qids[BUCKET:2 * BUCKET]}


def _by_name(spans, name):
    return [s for s in spans if s["name"] == name]


def test_every_forwarded_span_is_on_a_host_plane(traced):
    names = {name for name, _ in traced["events"]}
    assert set(PARENT) <= names
    # Ticket spans open and end on different threads: never forwarded.
    assert not {"ticket", "queue", "submit"} & names


def test_children_lie_inside_their_parents(traced):
    spans = traced["spans"]
    by_id = {s["id"]: s for s in spans}
    for name, parent in PARENT.items():
        got = _by_name(spans, name)
        assert got, name
        for s in got:
            if parent is None:
                continue
            p = by_id[s["parent"]]
            assert p["name"] == parent
            assert p["t0"] <= s["t0"] <= s["t1"] <= p["t1"], name
    (occ,) = _by_name(spans, "occupancy")
    assert occ["args"]["n_queries"] == BUCKET
    assert len(_by_name(spans, "scatter")) == BUCKET
    assert len(_by_name(spans, "pack")) == BUCKET


def test_input_build_children_cover_batch_inputs(traced):
    spans = traced["spans"]
    dur = lambda name: sum(s["t1"] - s["t0"] for s in _by_name(spans, name))
    covered = dur("occupancy") + dur("h2d") + dur("l1_dispatch")
    assert covered >= 0.9 * dur("batch_inputs")


def test_tracer_times_match_annotations_through_one_marker(traced):
    """Mapped through the marker alone, each span's tracer start lands
    within 1 ms of its annotation's start on the profiler's clock."""
    events = traced["events"]
    (m_ns,) = [t for name, t in events if name == MARKER]
    for name in PARENT:
        starts = sorted(t for n, t in events if n == name)
        spans = sorted(_by_name(traced["spans"], name), key=lambda s: s["t0"])
        assert len(starts) == len(spans), name
        for s, t_ns in zip(spans, starts):
            mapped = m_ns + (s["t0"] - traced["t_marker"]) * 1e9
            assert abs(t_ns - mapped) < 1e6, name


def test_null_tracer_records_nothing_and_changes_no_output(
        traced, tiny_system, tmp_path):
    sys_ = tiny_system
    qids = traced["qids"]
    engine = _engine(sys_, NULL_TRACER)
    with _profiled(tmp_path):
        engine.serve(qids)
    assert len(NULL_TRACER.log) == 0
    names = {name for name, _ in _host_events(tmp_path)}
    assert not set(PARENT) & names

    pol = _policy(sys_)
    ex = engine.executor
    tracer = Tracer()
    plain = sys_.batch_inputs(qids, span=NULL_SPAN)
    with tracer.span("t") as span:
        spanned = sys_.batch_inputs(qids, span=span)
    for a, b in zip(plain, spanned):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    out_plain = ex.execute(pol, *plain)
    with tracer.span("t") as span:
        out_spanned = ex.execute(pol, *plain, span=span)
    for a, b in zip(out_plain, out_spanned):
        np.testing.assert_array_equal(a, b)


def test_h2d_bytes_counts_the_arrays_copied(traced, tiny_system):
    sys_ = tiny_system
    occ, scores, tp = sys_.batch_inputs(traced["qids"])
    idf = sys_.idf_all[traced["qids"]]
    want = occ.nbytes + tp.nbytes + idf.nbytes
    t = sys_.log.terms.shape[1]
    assert want == BUCKET * (sys_.env_cfg.n_blocks * t * 4
                             * sys_.env_cfg.words_per_block * 4 + t + t * 4)
    (h2d,) = _by_name(traced["spans"], "h2d")
    assert h2d["args"]["bytes"] == want
    assert "t_execute_s" not in traced["engine"].telemetry.batches[-1]


class _StaleEpochs:
    """An index-epoch store whose every epoch reads as stale."""

    def validate(self, version):
        raise StaleIndexEpochError(f"epoch {version} is stale")


def test_stale_epoch_ends_its_microbatch_span(tiny_system, tmp_path):
    """A micro-batch refused for a stale index epoch is re-queued; its
    span ends with the error, and its annotation with it, on the thread
    that opened it."""
    sys_ = tiny_system
    pol = _policy(sys_)
    engine = ServeEngine(sys_, {c: pol for c in (CAT1, CAT2)}, EngineConfig(
        min_bucket=BUCKET, max_bucket=BUCKET, cache_capacity=0,
        auto_refresh=False), tracer=Tracer())
    engine.submit_many(np.where(sys_.log.category == CAT1)[0][:BUCKET])
    engine._index_store = _StaleEpochs()
    with _profiled(tmp_path):
        with pytest.raises(StaleIndexEpochError):
            engine.flush()
    assert engine.batcher.pending() == BUCKET
    assert engine.inflight == 0
    (mb,) = [s for s in engine.tracer.log.snapshot()
             if s["name"] == "microbatch"]
    assert mb["args"]["error"] == "StaleIndexEpochError"
    names = [name for name, _ in _host_events(tmp_path)]
    assert names.count("microbatch") == 1
    assert "batch_inputs" not in names


def test_failed_slab_ends_its_span(tiny_system, monkeypatch):
    sys_ = tiny_system
    engine = _engine(sys_, Tracer())

    def fail(key):
        raise RuntimeError("cache down")

    monkeypatch.setattr(engine.cache, "peek", fail)
    with pytest.raises(RuntimeError):
        engine.submit_many(np.where(sys_.log.category == CAT1)[0][:BUCKET])
    (slab,) = [s for s in engine.tracer.log.snapshot()
               if s["name"] == "slab"]
    assert slab["args"]["error"] == "RuntimeError"


# ------------------------------------------------ names the readers key on
def _hlo_without_metadata(text):
    """The computations of an HLO module's text, without the op
    metadata and the source-location tables before them."""
    body = text[re.search(r"^(%|ENTRY)", text, re.M).start():]
    return re.sub(r", metadata=\{[^}]*\}", "", body)


def test_serve_fn_scopes_and_module_name(traced, monkeypatch):
    from bench.trace_reduce import SERVE_MODULE

    ex = traced["engine"].executor
    args = ex._abstract_args(BUCKET, _policy(ex.system))
    lowered = ex._jit.lower(*args)
    text = lowered.as_text(debug_info=True)
    for scope in ("rollout", "merge", "l1_prune"):
        assert f"/{scope}/" in text, scope
    (module,) = re.findall(r"^module @(\S+)", text, re.M)
    assert SERVE_MODULE in module
    # No span or scope name can be mistaken for what the readers match.
    for name in (*PARENT, "rollout", "merge", "l1_prune"):
        assert SERVE_MODULE not in name and "block_scan_pruned" not in name

    # The scopes are metadata: without them the same HLO compiles.
    scoped = _hlo_without_metadata(lowered.compile().as_text())
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())

    def _serve_fn(bins, policy, occ, scores, term_present):
        # A new function, so it is traced afresh.
        return ex._serve_fn(bins, policy, occ, scores, term_present)

    bare = _hlo_without_metadata(
        jax.jit(_serve_fn).lower(*args).compile().as_text())
    assert scoped == bare


def test_scan_kernel_keeps_its_name():
    from repro.kernels.block_scan.block_scan_pruned import (
        block_scan_pruned_chunk, build_rule_meta)

    b, nb, t, f, w = 2, 4, 4, 4, 128
    sd = jax.ShapeDtypeStruct

    def chunk(o, a, r, p, bp):
        return block_scan_pruned_chunk(o, build_rule_meta(a, r, p, bp),
                                       chunk=2, n_terms=t, interpret=True)

    jaxpr = jax.make_jaxpr(chunk)(
        sd((b, nb, t * f, w), jnp.uint32), sd((b, t, f), jnp.bool_),
        sd((b, t), jnp.bool_), sd((b, t), jnp.bool_), sd((b,), jnp.int32))
    names = [e.params["name"] for e in jaxpr.jaxpr.eqns
             if e.primitive.name == "pallas_call"]
    assert names == ["block_scan_pruned_chunk"]
