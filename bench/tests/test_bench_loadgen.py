"""The traffic generator: query plans, fixed arrival counts, and latency
counted from the due time against a stalled server."""
import queue
import threading
import time

import numpy as np
import pytest

from bench import loadgen


class Ticket:
    def __init__(self):
        self._done = False

    def done(self):
        return self._done

    def result(self, timeout=None):
        return "ok"


class StalledServer:
    """Answers nothing for ``stall_s`` after its first request (its
    front door also blocks that long on the first submit), then answers
    every request ``service_s`` after it was sent or the stall ended."""

    def __init__(self, stall_s: float, service_s: float):
        self.q = queue.SimpleQueue()
        self.stall_s, self.service_s = stall_s, service_s
        self.t_resume = None
        self.timers = []

    def submit(self, qid):
        now = time.perf_counter()
        if self.t_resume is None:
            self.t_resume = now + self.stall_s
            time.sleep(self.stall_s)           # the front door stalls too
        t = Ticket()
        at = max(time.perf_counter(), self.t_resume) + self.service_s

        def finish():
            t._done = True
            self.q.put((t, "ok", time.perf_counter()))

        timer = threading.Timer(at - time.perf_counter(), finish)
        timer.start()
        self.timers.append(timer)
        return t


def test_open_loop_counts_latency_from_due_time():
    traffic = {"kind": "open", "arrivals": "poisson", "rate_qps": 40.0,
               "drain_s": 5}
    srv = StalledServer(stall_s=0.5, service_s=0.05)
    w = loadgen.drive(srv.submit, srv.q, traffic, np.arange(1000), 1.0,
                      seed=7, is_response=lambda r: r == "ok")
    offsets = loadgen.arrival_offsets(traffic, 1.0, 7)
    assert len(w.records) == len(offsets) == 40
    assert w.failed == 0
    lat = w.latencies_s()
    # The first request is due at its offset; the stall delays every
    # request due during it, and each is charged from its own due time.
    assert lat[0] >= 0.5
    stalled = [r for r in w.records if r.due < w.t0 + 0.5 + offsets[0]]
    assert len(stalled) > 5
    for r in stalled:
        assert r.done - r.due >= (w.t0 + offsets[0] + 0.5) - r.due
        assert r.sent - r.due >= 0.0
    # The generator ran late through the stall, and says so.
    assert np.max(w.lateness_s()) >= 0.4
    # A request that never comes counts as infinitely late.
    late = loadgen.Window(0.0, 1.0, [loadgen.Record(qid=0, due=0.0)])
    assert late.failed == 1 and np.isinf(late.latencies_s()).all()
    assert loadgen.nearest_rank(late.latencies_s(), 0.95) == np.inf


def test_closed_loop_keeps_the_clients_busy():
    traffic = {"kind": "closed", "outstanding": 4, "settle_s": 0.005,
               "drain_s": 5}
    srv = StalledServer(stall_s=0.0, service_s=0.02)
    w = loadgen.drive(srv.submit, srv.q, traffic, np.arange(10_000), 0.5,
                      seed=1, is_response=lambda r: r == "ok")
    assert w.failed == 0
    # About 0.5 s / 0.02 s x 4 clients, give or take scheduling.
    assert 40 <= len(w.records) <= 110
    assert w.qps() == pytest.approx(len(w.records) /
                                    (max(r.done for r in w.records) - w.t0))


def test_arrival_count_is_fixed_by_rate_and_window():
    traffic = {"arrivals": "poisson", "rate_qps": 9.0}
    a = loadgen.arrival_offsets(traffic, 40.0, 2**31 + 5)
    b = loadgen.arrival_offsets(traffic, 40.0, 12345)
    assert len(a) == len(b) == 360
    assert np.all(np.diff(a) >= 0) and 0 <= a[0] and a[-1] < 40.0
    assert not np.array_equal(a, b)


def test_unique_plan_alternates_categories_and_never_repeats_a_key():
    rng = np.random.default_rng(0)
    terms = rng.integers(-1, 6, size=(400, 4))
    cat = rng.integers(0, 2, size=400)
    traffic = {"select": "unique"}
    seq = loadgen.plan_queries(terms, cat, None, traffic, 3, exclude=[0, 1])
    keys = [loadgen._canonical(terms[q], cat[q]) for q in seq]
    assert len(set(keys)) == len(keys)
    assert loadgen._canonical(terms[0], cat[0]) not in keys
    assert list(cat[seq][:6]) == [0, 1, 0, 1, 0, 1]
    other = loadgen.plan_queries(terms, cat, None, traffic, 4)
    assert not np.array_equal(seq[:20], other[:20])


def test_popularity_plan_repeats_head_queries():
    pop = np.array([0.9, 0.05, 0.05])
    seq = loadgen.plan_queries(np.zeros((3, 4)), np.zeros(3), pop,
                               {"select": "popularity", "sequence_len": 200}, 0)
    assert len(seq) == 200 and (seq == 0).mean() > 0.7
