"""The one traffic generator: every mix is a data file it reads.

A mix (``bench/traffic/<name>.json``) sets:

``kind``
    ``"closed"``: ``outstanding`` queries kept in flight (capacity).  A
    burst of answers is replaced by one slab of new queries once no
    answer has landed for ``settle_s``: a bounded form of the MLPerf
    Inference "Offline" scenario, where the load generator hands the
    system its queries in bulk and only throughput counts, so how the
    server coalesces a trickle of arrivals does not enter.  ``"open"``:
    ``rate_qps`` independent arrivals per second, sent on their
    schedule whatever the server does (tails).
``arrivals``
    ``"poisson"``: a fixed count, ``round(rate_qps * seconds)``, of
    arrival times drawn uniformly over the window and sorted -- a
    Poisson process conditioned on its count, so every seed offers the
    same amount of work in another order.
``select``
    ``"unique"``: each query of the log at most once (no two sent
    queries share a cache key), categories alternating so that every
    seed sends the same category mix.  ``"popularity"``: drawn with
    repeats by the log's popularity, so head queries repeat.
``drain_s``
    How long past the window's close the generator waits for answers.

Latency counts from when a request was *due*, not from when it was
sent, so a stalled generator or front door shows as latency.  Sent
times are kept to report how late the generator ran.
"""
from __future__ import annotations

import dataclasses
import queue
import time
from typing import Callable, Dict, List, Optional

import numpy as np

__all__ = ["Record", "Window", "plan_queries", "arrival_offsets",
           "hook_completions", "drive", "nearest_rank"]


@dataclasses.dataclass
class Record:
    qid: int
    due: float
    sent: float = float("nan")
    done: Optional[float] = None
    result: object = None          # what the server answered (or None)
    ok: bool = False               # answered with a response (not a shed)


@dataclasses.dataclass
class Window:
    t0: float                      # window start (host clock)
    t_close: float                 # t0 + seconds
    records: List[Record]

    @property
    def completed(self) -> List[Record]:
        return [r for r in self.records if r.ok]

    @property
    def failed(self) -> int:
        return sum(not r.ok for r in self.records)

    def latencies_s(self) -> np.ndarray:
        """Completion minus due time; a failed or unanswered request
        counts as infinitely late."""
        return np.array([(r.done - r.due) if r.ok else np.inf
                         for r in self.records], np.float64)

    def qps(self) -> float:
        done = [r.done for r in self.completed]
        return len(done) / (max(done) - self.t0) if done else 0.0

    def lateness_s(self) -> np.ndarray:
        return np.array([r.sent - r.due for r in self.records], np.float64)


def nearest_rank(values, q: float) -> float:
    """The q-quantile by nearest rank (ceil(q*n)-th smallest): always a
    value that was observed, infinite entries included."""
    v = np.sort(np.asarray(values, np.float64))
    if not len(v):
        return float("nan")
    return float(v[max(0, int(np.ceil(q * len(v))) - 1)])


def _canonical(terms_row, category) -> tuple:
    t = np.asarray(terms_row).ravel()
    return (int(category), tuple(sorted({int(x) for x in t if x >= 0})))


def plan_queries(terms: np.ndarray, category: np.ndarray,
                 popularity: np.ndarray, traffic: dict, seed: int,
                 exclude=()) -> np.ndarray:
    """The seed's sequence of query ids for a mix.  ``exclude`` holds
    query ids whose cache keys the sequence must not share (the
    warm-up's)."""
    rng = np.random.default_rng([seed, 1])
    select = traffic["select"]
    if select == "popularity":
        n = int(traffic.get("sequence_len", 65536))
        return rng.choice(len(terms), size=n, p=popularity).astype(np.int64)
    if select != "unique":
        raise ValueError(f"unknown select {select!r}")
    taken = {_canonical(terms[q], category[q]) for q in exclude}
    per_cat: Dict[int, List[int]] = {}
    for q in rng.permutation(len(terms)):
        key = _canonical(terms[q], category[q])
        if key in taken:
            continue
        taken.add(key)
        per_cat.setdefault(int(category[q]), []).append(int(q))
    cats = sorted(per_cat)
    n_each = min(len(per_cat[c]) for c in cats)
    # Alternate the categories so every prefix holds the same mix.
    return np.array([per_cat[c][i] for i in range(n_each) for c in cats],
                    np.int64)


def arrival_offsets(traffic: dict, seconds: float, seed: int) -> np.ndarray:
    """Seconds after the window start at which each open-loop request
    is due."""
    if traffic["arrivals"] != "poisson":
        raise ValueError(f"unknown arrivals {traffic['arrivals']!r}")
    n = int(round(float(traffic["rate_qps"]) * seconds))
    rng = np.random.default_rng([seed, 2])
    return np.sort(rng.uniform(0.0, seconds, size=n))


def hook_completions(replica_set) -> "queue.SimpleQueue":
    """A queue that receives ``(ticket, result, t)`` for every ticket a
    replica completes, ``t`` read from the host clock at completion."""
    q: "queue.SimpleQueue" = queue.SimpleQueue()
    for rep in replica_set.replicas:
        inner = rep.on_complete

        def on_complete(ticket, result, inner=inner):
            q.put((ticket, result, time.perf_counter()))
            if inner is not None:
                inner(ticket, result)

        rep.on_complete = on_complete
    return q


def drive(submit: Callable[[int], object], done_q: "queue.SimpleQueue",
          traffic: dict, sequence: np.ndarray, seconds: float, seed: int,
          is_response: Callable[[object], bool],
          on_start: Callable[[float], None] = lambda t0: None,
          clock: Callable[[], float] = time.perf_counter,
          submit_many: Optional[Callable[[List[int]], list]] = None
          ) -> Window:
    """Offer the mix for ``seconds`` through ``submit(qid) -> ticket``
    (and ``submit_many(qids) -> tickets`` for the closed loop's slabs),
    then wait up to ``drain_s`` for every request sent.  Completions
    arrive on ``done_q``; a ticket that is already done when ``submit``
    returns (shed at admission) completes there."""
    submit_many = submit_many or (lambda qids: [submit(q) for q in qids])
    records: List[Record] = []
    # Keyed by the ticket itself (identity hash), which also keeps every
    # ticket alive for the run: an id() could be reused after a GC.
    by_ticket: Dict[object, Record] = {}
    n_open = [0]

    def register(ticket, rec: Record) -> None:
        rec.sent = clock()
        records.append(rec)
        by_ticket[ticket] = rec
        n_open[0] += 1
        if ticket.done():
            _finish(ticket, ticket.result(0), rec.sent)

    def send(qid: int, due: float) -> None:
        rec = Record(qid=int(qid), due=due)
        register(submit(int(qid)), rec)

    def send_slab(n: int) -> None:
        due = clock()
        qids = [next_qid() for _ in range(n)]
        for q, ticket in zip(qids, submit_many(qids)):
            register(ticket, Record(qid=q, due=due))

    def _finish(ticket, result, t: float) -> bool:
        rec = by_ticket.get(ticket)
        if rec is None or rec.done is not None:
            return False
        rec.done, rec.result, rec.ok = t, result, is_response(result)
        n_open[0] -= 1
        return True

    def take(timeout: float) -> bool:
        try:
            ticket, result, t = done_q.get(timeout=max(timeout, 0.0))
        except queue.Empty:
            return False
        return _finish(ticket, result, t)

    kind = traffic["kind"]
    it = iter(sequence)

    def next_qid() -> int:
        try:
            return int(next(it))
        except StopIteration:
            raise ValueError(f"the query log ran out after {len(records)} "
                             "queries: size it to what the mix sends") from None

    t0 = clock()
    on_start(t0)
    t_close = t0 + seconds
    if kind == "closed":
        settle = float(traffic["settle_s"])
        send_slab(int(traffic["outstanding"]))
        while (now := clock()) < t_close:
            n = int(take(t_close - now))
            if not n:
                continue
            while take(settle):
                n += 1
            if clock() < t_close:
                send_slab(n)
    elif kind == "open":
        offsets = arrival_offsets(traffic, seconds, seed)
        if len(offsets) > len(sequence):
            raise ValueError(f"the mix sends {len(offsets)} queries but the "
                             f"sequence holds {len(sequence)}")
        for off in offsets:
            due = t0 + float(off)
            while (now := clock()) < due:
                take(due - now)
            send(next_qid(), due)
    else:
        raise ValueError(f"unknown traffic kind {kind!r}")
    deadline = t_close + float(traffic["drain_s"])
    while n_open[0] and (now := clock()) < deadline:
        take(deadline - now)
    return Window(t0=t0, t_close=t_close, records=records)
