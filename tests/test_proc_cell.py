"""Multi-process serving cell: SPSC shm rings, binary codecs, worker
fault tolerance, publish-relay ordering, thread-vs-process bit-parity,
delta-aware admission pricing, and op-log crash-restart parity
(src/repro/cluster/proc/, docs/cluster.md)."""
import os
import signal
import time

import numpy as np
import pytest

from repro.cluster import ClusterConfig, ReplicaSet, Shed, UCostEstimator
from repro.cluster.proc import (REQUEST_BYTES, ProcessReplica, ShmRing,
                                decode_request, decode_response,
                                encode_request, encode_response,
                                response_bytes)
from repro.cluster.proc.ring import RingClosed
from repro.cluster.replica import ClusterTicket
from repro.data.querylog import CAT1, CAT2
from repro.policies import PolicyStore, TabularQPolicy
from repro.serving import EngineConfig, ServiceLevel
from repro.serving.engine import ServeResponse

from test_serving import _direct


@pytest.fixture(scope="module")
def trained(tiny_system):
    policies = {cat: TabularQPolicy(tiny_system.train_policy(cat, iters=10,
                                                             batch=16)[0])
                for cat in (CAT1, CAT2)}
    return tiny_system, policies


def _store(policies, staleness_bound=4, fallbacks=None):
    store = PolicyStore(staleness_bound=staleness_bound)
    store.publish(dict(policies), fallbacks=fallbacks)
    return store


# ------------------------------------------------------------------- rings
def test_ring_wraparound_preserves_fifo():
    """Sequence-number recycling survives several full laps of a tiny
    ring, interleaved full/empty conditions included."""
    ring = ShmRing.create(4, slot_bytes=16)
    try:
        sent = recvd = 0
        for lap in range(5):                   # 20 messages through 4 slots
            while ring.try_push(f"m{sent:04d}".encode()):
                sent += 1
            assert not ring.try_push(b"overflow")      # full: refused
            assert ring.occupancy() == 4
            while (msg := ring.try_pop()) is not None:
                assert msg == f"m{recvd:04d}".encode()  # strict FIFO
                recvd += 1
        assert sent == recvd == 20
        assert ring.try_pop() is None                   # empty: None
    finally:
        ring.close()


def test_ring_rejects_oversized_payload_before_write():
    ring = ShmRing.create(4, slot_bytes=8)
    try:
        with pytest.raises(ValueError, match="codec layer"):
            ring.try_push(b"x" * 9)
        assert ring.occupancy() == 0           # nothing partially written
        ring.push(b"x" * 8)                    # exactly slot_bytes is fine
        assert ring.try_pop() == b"x" * 8
    finally:
        ring.close()


def test_ring_park_counters_and_liveness():
    ring = ShmRing.create(2, slot_bytes=4)
    try:
        ring.push(b"a")
        ring.push(b"b")
        # full ring + dead peer: the producer parks, then bails out
        with pytest.raises(RingClosed):
            ring.push(b"c", alive=lambda: False)
        assert ring.park_stats()["producer_parks"] >= 1
        # drained ring + dead peer: the consumer parks, then bails out
        ring.try_pop(), ring.try_pop()
        with pytest.raises(RingClosed):
            ring.pop(alive=lambda: False)
        assert ring.park_stats()["consumer_parks"] >= 1
        ring.set_depth_hint(7)
        assert ring.depth_hint() == 7
        ring.stamp_heartbeat()
        assert ring.heartbeat() > 0
    finally:
        ring.close()


def test_ring_closed_raises():
    ring = ShmRing.create(2, slot_bytes=4)
    ring.close()
    with pytest.raises(RingClosed):
        ring.try_push(b"a")
    with pytest.raises(RingClosed):
        ring.try_pop()
    ring.close()                               # idempotent


# ------------------------------------------------------------------ codecs
def test_request_codec_roundtrip():
    payload = encode_request(77, 1234, ServiceLevel.SHALLOW, 2)
    assert len(payload) == REQUEST_BYTES
    # trace_root defaults to 0 = tracing off
    assert decode_request(payload) == (77, 1234, ServiceLevel.SHALLOW, 2, 0)
    # trace context (a 64-bit span id) rides the record unchanged
    root = (1 << 40) + 17
    payload = encode_request(77, 1234, ServiceLevel.FULL, 1, root)
    assert len(payload) == REQUEST_BYTES
    assert decode_request(payload) == (77, 1234, ServiceLevel.FULL, 1, root)


def test_response_codec_roundtrip_and_truncation_guard():
    r = ServeResponse(
        request_id=0, qid=42, category=1,
        doc_ids=np.array([5, 9, -1], np.int32),
        scores=np.array([2.5, 1.5, 0.0], np.float32),
        u=128, cand_cnt=17, cached=True, latency_s=0.25,
        policy_version=3, index_epoch=2, level=ServiceLevel.SHALLOW)
    tid, back = decode_response(encode_response(9, r, keep=4))
    assert tid == 9 and back.qid == 42 and back.category == 1
    np.testing.assert_array_equal(back.doc_ids, r.doc_ids)
    np.testing.assert_array_equal(back.scores, r.scores)
    assert (back.u, back.cand_cnt, back.cached) == (128, 17, True)
    assert (back.policy_version, back.index_epoch) == (3, 2)
    assert back.level == ServiceLevel.SHALLOW
    assert back.latency_s == 0.25
    # a response wider than the ring slots were sized for must be
    # rejected at encode time, never silently truncated
    with pytest.raises(ValueError, match="keep"):
        encode_response(9, r, keep=2)


def test_shed_codec_roundtrip():
    shed = Shed(7, 1, 33.5, "replica_queue_full")
    tid, back = decode_response(
        encode_response(3, shed, keep=8))
    assert tid == 3 and isinstance(back, Shed)
    assert (back.qid, back.category) == (7, 1)
    assert back.est_u == 33.5
    assert back.reason == "replica_queue_full"
    # shed payloads fit the fixed header regardless of keep
    assert len(encode_response(3, shed, keep=0)) == response_bytes(0)


# ------------------------------------- telemetry double-count (regression)
def test_ticket_complete_is_first_wins():
    """A requeued ticket can receive two answers (the original raced
    the death detection); only the first completion may count."""
    t = ClusterTicket(1, 0)
    r1 = ServeResponse(0, 1, 0, np.zeros(1, np.int32),
                       np.zeros(1, np.float32), 1, 1, False, 0.0)
    assert t.complete(r1) is True
    assert t.complete(Shed(1, 0, 0.0, "late duplicate")) is False
    assert t.result() is r1                    # first answer sticks


def test_duplicate_answer_not_double_counted():
    """ProcessReplica._finish gates bookkeeping AND the cluster
    callback on the ticket's first-completion — the bench/telemetry
    double-count bug when a ticket was answered twice after a worker
    death."""
    seen = []
    pr = ProcessReplica(0, spec_factory=None,
                        on_complete=lambda t, r: seen.append(r), keep=4)
    t = ClusterTicket(5, 0)
    resp = ServeResponse(0, 5, 0, np.zeros(1, np.int32),
                         np.zeros(1, np.float32), 1, 1, False, 0.0)
    pr._finish(t, resp)
    pr._finish(t, resp)                        # the requeue's duplicate
    assert pr.n_completed == 1
    assert len(seen) == 1


# ------------------------------------------------------- process cell E2E
def test_process_backend_bit_parity_with_thread(trained):
    """FULL responses through worker processes are bit-identical to the
    thread backend AND to the single-host reference rollout."""
    sys_, policies = trained
    rng = np.random.default_rng(4)
    qids = rng.integers(0, sys_.log.n_queries, size=24)
    results = {}
    for backend in ("thread", "process"):
        cluster = ReplicaSet(sys_, _store(policies),
                             ClusterConfig(n_replicas=2, backend=backend),
                             EngineConfig(min_bucket=8, max_bucket=8,
                                          cache_capacity=0))
        with cluster:
            results[backend] = cluster.serve(list(qids))
        stats = cluster.stats()
        assert stats["n_submitted"] == stats["n_responses"] == len(qids)
        if backend == "process":
            pids = {s["worker_pid"] for s in stats["replicas"]}
            assert len(pids) == 2 and os.getpid() not in pids
    ids, sc, u = _direct(sys_, policies, qids)
    for lane, (t, p) in enumerate(zip(results["thread"],
                                      results["process"])):
        assert not isinstance(t, Shed) and not isinstance(p, Shed)
        assert t.qid == p.qid == qids[lane]
        np.testing.assert_array_equal(p.doc_ids, t.doc_ids)
        np.testing.assert_array_equal(p.scores, t.scores)
        assert p.u == t.u == u[lane]
        np.testing.assert_array_equal(p.doc_ids, ids[lane])
        assert p.policy_version == 1


def test_process_cell_metrics_fold_worker_registries(trained):
    """Per-process registry snapshots (engine instruments + ring
    contention counters) merge through the existing fold."""
    sys_, policies = trained
    cluster = ReplicaSet(sys_, _store(policies),
                         ClusterConfig(n_replicas=1, backend="process"),
                         EngineConfig(min_bucket=4, max_bucket=8,
                                      cache_capacity=8))
    with cluster:
        cluster.serve(list(range(8)))
        snap = cluster.metrics_snapshot()
    keys = set(snap)
    assert any(k.startswith("serve.requests") for k in keys)
    assert any(k.startswith("ring.occupancy") for k in keys)
    assert any(k.startswith("ring.consumer_parks") for k in keys)
    assert any(k.startswith("cluster.submitted") for k in keys)


def test_process_cell_merged_trace_cross_pid(tmp_path, trained):
    """Tentpole E2E: trace context rides the ring request structs into
    the workers, worker spans ship back as deltas, and the parent merges
    everything into ONE timeline — at least one ticket must carry the
    full admit -> ring -> worker -> execute -> respond chain across the
    process boundary, with worker spans from >= 2 distinct pids."""
    from repro.obs import Tracer
    from test_obs import _load_checker

    sys_, policies = trained
    tracer = Tracer()
    cluster = ReplicaSet(sys_, _store(policies),
                         ClusterConfig(n_replicas=2, backend="process"),
                         EngineConfig(min_bucket=8, max_bucket=8,
                                      cache_capacity=0),
                         tracer=tracer)
    rng = np.random.default_rng(11)
    with cluster:
        results = cluster.serve(rng.integers(0, sys_.log.n_queries,
                                             size=24))
        assert not any(isinstance(r, Shed) for r in results)

        # the ping handshake landed a finite clock-offset sample
        for r in cluster.replicas:
            offset, rtt = r.clock_offset()
            assert rtt < 10.0 and abs(offset) < 10.0

        # stats round trips carry the workers' trace deltas parent-side
        def merged_worker_pids():
            wpids = set()
            for e in cluster.trace_entries():
                if str(e["track"]).startswith("ticket #") \
                        and e["name"] == "worker":
                    wpids.add((e["args"] or {}).get("wpid"))
            wpids.discard(None)
            return wpids

        deadline = time.monotonic() + 60.0
        while time.monotonic() < deadline:
            cluster.stats()
            if len(merged_worker_pids()) >= 2:
                break
            time.sleep(0.05)
        worker_pids = merged_worker_pids()
        assert len(worker_pids) >= 2, f"worker spans from {worker_pids}"
        assert os.getpid() not in worker_pids

        # health plane reads clean while the cell is live
        doc = cluster.statusz()
        assert doc["backend"] == "process" and doc["state"] != "dead"
        assert {r["worker_pid"] for r in doc["replicas"]} >= worker_pids
        for r in doc["replicas"]:
            assert r["state"] in ("healthy", "parked_idle", "busy")

        # the exported single file passes the cross-process chain gate
        path = tmp_path / "proc_trace.json"
        n = cluster.write_trace(path)
        assert n > 0
    out = _load_checker().check_trace(str(path), require_chain=False,
                                      require_proc_chain=True)
    assert out["n_proc_chain_tickets"] >= 1
    assert len(out["worker_pids"]) >= 2
    assert str(out["example_proc_chain_track"]).startswith("ticket #")


def test_worker_sigkill_respawns_and_no_ticket_drops(trained):
    """SIGKILL mid-stream: outstanding tickets are requeued to the
    respawned worker (or explicitly shed) — never dropped — the fresh
    worker serves correctly, and the salvage leaves a postmortem bundle
    behind (metrics snapshot + trace tail + event-ring tail)."""
    from repro.obs import Tracer

    sys_, policies = trained
    cluster = ReplicaSet(sys_, _store(policies),
                         ClusterConfig(n_replicas=1, backend="process",
                                       max_worker_restarts=2),
                         EngineConfig(min_bucket=8, max_bucket=8,
                                      cache_capacity=0),
                         tracer=Tracer())
    with cluster:
        replica = cluster.replicas[0]
        first = cluster.serve(list(range(8)))
        assert not any(isinstance(r, Shed) for r in first)
        # a stats round trip lands the first wave's metrics + worker
        # trace delta parent-side — what the bundle must preserve
        cluster.stats()
        pid_before = replica.worker_pid

        # kill with tickets in flight: the requeue path must absorb it
        tickets = [cluster.submit(q) for q in range(8, 24)]
        os.kill(pid_before, signal.SIGKILL)
        results = [t.result(timeout=600.0) for t in tickets]
        assert all(r is not None for r in results), "dropped tickets"

        deadline = time.monotonic() + 600.0
        while replica.n_restarts < 1 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert replica.n_restarts >= 1
        assert replica.worker_pid != pid_before

        # the respawned worker serves bit-identically
        again = cluster.serve(list(range(8)))
        assert not any(isinstance(r, Shed) for r in again)
        for a, b in zip(first, again):
            np.testing.assert_array_equal(a.doc_ids, b.doc_ids)
        # complete() unblocks ticket.result() BEFORE the collector runs
        # on_complete, so the fleet counters are eventually consistent
        # with resolved tickets — poll briefly before the equality check
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            stats = cluster.stats()
            if stats["n_submitted"] == \
                    stats["n_responses"] + stats["n_shed"]:
                break
            time.sleep(0.01)
        assert stats["n_submitted"] == \
            stats["n_responses"] + stats["n_shed"]
        assert stats["replicas"][0]["n_restarts"] >= 1

        # crash forensics: the salvage dumped a postmortem bundle with
        # the dead worker's last metrics, its trace tail (rebased spans
        # from the first wave), and the fleet event-ring tail
        import json
        assert replica.last_bundle_path is not None
        bundle = json.loads(open(replica.last_bundle_path).read())
        assert bundle["reason"] == "worker_dead"
        assert bundle["worker_pid"] == pid_before
        assert bundle["death_traceback"] is None   # SIGKILL leaves none
        assert bundle["config"]["backend"] == "process"
        assert any(k.startswith("serve.requests")
                   for k in bundle["metrics"]), "no metrics snapshot"
        assert bundle["trace_tail"], "no trace tail in bundle"
        assert all("wpid" in (e["args"] or {}) for e in bundle["trace_tail"]
                   if str(e["track"]).startswith("ticket #"))
        kinds = [e["kind"] for e in bundle["events_tail"]]
        assert "worker_dead" in kinds
        # ...and the live event ring saw the respawn too
        all_kinds = {e["kind"] for e in cluster.events.tail()}
        assert {"worker_dead", "worker_restart"} <= all_kinds


def test_stale_policy_relay_is_skipped_not_applied(trained):
    """Control-channel ordering: a worker applies publishes
    monotonically — a late v_old relay after v_new must be a no-op (the
    worker-local store enforces publish-if-newer)."""
    sys_, policies = trained
    store = _store(policies)
    cluster = ReplicaSet(sys_, store,
                         ClusterConfig(n_replicas=1, backend="process"),
                         EngineConfig(min_bucket=8, max_bucket=8,
                                      cache_capacity=0))
    with cluster:
        replica = cluster.replicas[0]
        snap = store.snapshot()
        pols, fbs = dict(snap.policies), dict(snap.fallbacks)
        replica.relay_policy(5, pols, fbs)     # future version
        replica.relay_policy(3, pols, fbs)     # stale: must be skipped
        deadline = time.monotonic() + 60.0
        while replica.policy_version < 5 and time.monotonic() < deadline:
            time.sleep(0.02)
        assert replica.policy_version == 5
        res = cluster.serve([0, 1, 2, 3])
        assert not any(isinstance(r, Shed) for r in res)
        assert all(r.policy_version == 5 for r in res)


# -------------------------------------------- delta-aware admission pricing
@pytest.fixture(scope="module")
def live_sys():
    from repro.data.querylog import QueryLogConfig
    from repro.index.corpus import CorpusConfig
    from repro.index.live import LiveRetrievalSystem
    from repro.system import SystemConfig

    return LiveRetrievalSystem(SystemConfig(
        corpus=CorpusConfig(n_docs=256, vocab_size=128, seed=7),
        querylog=QueryLogConfig(n_queries=64, seed=7),
        block_docs=64, p_bins=64, u_budget=256, l1_steps=40,
    ), capacity_docs=768)


def _doc_with_terms(terms, vocab=128):
    body = np.unique(np.asarray(terms, np.int32))
    other = np.array([int(body[0])], np.int32)
    return [other, other, body, other]


def test_ucost_delta_correction_converges(live_sys):
    """A query whose terms land in the head delta is priced with a
    learned per-category correction; base buckets stay base-only and
    non-hit queries are unaffected."""
    est = UCostEstimator(live_sys, prior_u=100.0)
    log = live_sys.log
    qid = 0
    hit_terms = log.terms[qid, : log.n_terms[qid]]
    # a second query sharing no terms with the delta doc
    other = next(q for q in range(log.n_queries)
                 if not set(log.terms[q, : log.n_terms[q]].tolist())
                 & set(hit_terms.tolist())
                 and (int(log.category[q]), est.features(q)[1])
                 == (int(log.category[qid]), est.features(qid)[1]))

    est.observe(qid, 100.0)                    # base-only: table = 100
    assert est.estimate(qid) == 100.0
    assert not est.delta_hit(qid)

    live_sys.add_documents([_doc_with_terms(hit_terms)])
    head = live_sys.commit_index()
    assert est.delta_hit(qid)
    assert not est.delta_hit(other)
    assert est.estimate(qid) == 100.0          # correction starts at 1.0

    # outcomes stamped at a STALE epoch never train the correction
    est.observe(qid, 500.0, index_epoch=head - 1)
    assert est.estimate(qid) == 100.0

    # head-epoch outcomes converge the estimate onto the realized u
    for _ in range(12):
        est.observe(qid, 160.0, index_epoch=head)
    assert abs(est.estimate(qid) - 160.0) < 1.0
    # same bucket, no delta terms: priced from the untouched base table
    assert est.estimate(other) == 100.0
    d = est.describe()
    assert d["delta_obs"] == 12 and d["delta_terms_epoch"] == head

    # a merge empties the delta: pricing falls back to the clean table
    live_sys.merge_index()
    assert not est.delta_hit(qid)
    assert est.estimate(qid) == 100.0


# ------------------------------------------- op-log checkpoint / restore
def test_oplog_checkpoint_restore_bit_parity(tmp_path):
    """Crash-restart: restore() replays the committed op-log prefix and
    the head view is bit-identical to the never-crashed index's;
    pending (uncommitted) ops survive to the next commit."""
    from repro.index.corpus import N_FIELDS
    from repro.index.live import LiveIndex
    from test_live_index import rand_doc, tiny_index

    rng = np.random.default_rng(3)
    live = LiveIndex(tiny_index(n_docs=96), storage_dir=tmp_path / "cell")
    live.add_documents([rand_doc(rng) for _ in range(5)])
    live.commit()
    live.update_document(7, rand_doc(rng))
    live.commit()
    live.add_documents([rand_doc(rng) for _ in range(2)])  # pending
    live.checkpoint()

    restored = LiveIndex.restore(tmp_path / "cell")
    a = live.store.snapshot().view
    b = restored.store.snapshot().view
    assert a.n_docs == b.n_docs
    np.testing.assert_array_equal(a.df, b.df)
    np.testing.assert_array_equal(a.static_rank(), b.static_rank())
    np.testing.assert_array_equal(a.doc_len(), b.doc_len())
    vocab = a.base.index.vocab_size
    for f in range(N_FIELDS):
        for term in range(vocab):
            np.testing.assert_array_equal(a.postings(term, f),
                                          b.postings(term, f))
    # pending ops were checkpointed too: committing them lands the same
    # docs at the same ids on both sides
    assert live.commit() > 0 and restored.commit() > 0
    av = live.store.snapshot().view
    bv = restored.store.snapshot().view
    assert av.n_docs == bv.n_docs
    for f in range(N_FIELDS):
        for term in range(vocab):
            np.testing.assert_array_equal(av.postings(term, f),
                                          bv.postings(term, f))


# ------------------------------------------------- one process per chip
def test_process_backend_refuses_on_tpu(tiny_system, monkeypatch):
    """A TPU belongs to one process, and the parent already holds it:
    with a TPU default backend the process cell raises before it
    creates any worker (or even its rings)."""
    import jax

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")

    def no_worker(*a, **k):
        raise AssertionError("a process replica was created on a TPU host")

    monkeypatch.setattr(ProcessReplica, "__init__", no_worker)
    store = _store({cat: TabularQPolicy(np.zeros((4, 4), np.float32))
                    for cat in (CAT1, CAT2)})
    with pytest.raises(RuntimeError, match="one process at a time"):
        ReplicaSet(tiny_system, store, ClusterConfig(backend="process"))
    monkeypatch.undo()
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    pr = ProcessReplica(0, spec_factory=None, keep=4)
    with pytest.raises(RuntimeError, match="one process at a time"):
        pr.start()
    assert pr._proc is None and pr._req is None and pr.worker_pid is None
