"""``correct`` comes out false when the timed path is broken underneath
a whole run (the device stamp bypassed, nothing else), once for each
fault a one-chip serving cell can have, and for the control: the L1
ranker's matmuls at ``high`` precision."""
import jax
import numpy as np
import pytest

from bench import check, spec
from bench.tests import tiny


def state_unchanged(ids, sc, u, cnt):
    """The rollout returns its initial state: nothing scanned."""
    return (np.full_like(ids, -1), np.full_like(sc, -np.inf),
            np.zeros_like(u), np.zeros_like(cnt))


def half_batch_left_out(ids, sc, u, cnt):
    """Only the first half of the lanes is computed; the rest repeat it."""
    half = max(1, len(ids) // 2)
    idx = np.arange(len(ids)) % half
    return ids[idx], sc[idx], u[idx], cnt[idx]


def answer_altered(ids, sc, u, cnt):
    """One served id changed where it is produced."""
    ids = ids.copy()
    ids[0, 0] = ids[0, 0] + 1 if ids[0, 0] >= 0 else 0
    return ids, sc, u, cnt


@pytest.mark.parametrize("fault", [state_unchanged, half_batch_left_out,
                                   answer_altered])
def test_fault_makes_the_run_incorrect(monkeypatch, tmp_path, fault):
    from repro.serving.executor import ShardedExecutor

    execute = ShardedExecutor.execute
    monkeypatch.setattr(ShardedExecutor, "execute",
                        lambda self, *a, **k: fault(*execute(self, *a, **k)))
    root = tiny.make_root(tmp_path, tiny.CLOSED)
    with tiny.cpu_harness(monkeypatch, root) as br:
        res = br.run_cell(spec.load_cell("tiny.t", root), 97, 1.5, False,
                          tiny.STAMP, jax.devices()[:1], out=lambda s: None,
                          err=lambda s: None)
    assert res["correct"] is False
    assert any(c["value"] > c["limit"] for c in res["check"].values())


def test_high_precision_control_is_refused(monkeypatch, tmp_path):
    """The reference in the program's place, its L1 matmuls in three
    bfloat16 passes, fails the configuration's limits; the program,
    at ``highest``, passes them."""
    from bench.control import readings

    root = tiny.make_root(tmp_path, tiny.CLOSED)
    cell = spec.load_cell("tiny.t", root)
    with tiny.cpu_harness(monkeypatch, root) as br:
        h = br.Harness(cell, 5, False, jax.devices()[:1])
        try:
            window, *_ = h.measure(cell.traffic, 1.5)
        finally:
            h.close()
    got = readings(h, window.completed)
    limits = cell.config["correct"]["limits"]
    assert check.verdict(got["program"], limits, 0)[0] is True
    assert check.verdict(got["control"], limits, 0)[0] is False
