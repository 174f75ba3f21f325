"""Whole runs of a tiny cell on the CPU through the harness (the device
stamp is the only part bypassed): the last line's schema, a cell built
only from new files, and refusal without a TPU."""
import json

import jax
import pytest

from bench import spec
from bench.tests import tiny

E2E = {"setup_s", "qps", "p95_ms"}


def _run(monkeypatch, root, trace, seconds=2.0, seed=2**31 + 11):
    lines = []
    with tiny.cpu_harness(monkeypatch, root) as br:
        cell = spec.load_cell("tiny.t", root)
        res = br.run_cell(cell, seed, seconds, trace, tiny.STAMP,
                          jax.devices()[:1], out=lines.append,
                          err=lambda s: None)
    assert json.loads(lines[-1]) == json.loads(json.dumps(res))
    return res


def test_last_line_schema_untraced(monkeypatch, tmp_path):
    res = _run(monkeypatch, tiny.make_root(tmp_path, tiny.CLOSED), False)
    assert list(res)[:5] == ["correct", "attempted", "failed", "metrics",
                             "device"]
    assert list(res)[-1] == "check"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert set(res["metrics"]) == E2E
    for m in res["metrics"].values():
        assert set(m) == {"value", "unit"} and m["value"] > 0
    assert set(res["device"]) == {"platform", "kind", "count",
                                  "memory_peak_bytes"}
    for name, c in res["check"].items():
        assert set(c) == {"value", "limit"} and c["value"] <= c["limit"], name


def test_last_line_schema_traced(monkeypatch, tmp_path):
    res = _run(monkeypatch, tiny.make_root(tmp_path, tiny.OPEN), True)
    assert res["correct"] is True
    assert {"busy_s", "window_s"} <= set(res["device"])
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert all(len(v) <= 10 for v in res["breakdown"].values())
    names = set(res["metrics"])
    assert not names & E2E
    # Host-side readers find something on the CPU; device readers don't.
    assert {"batch_fill.qps", "inputs_host_ms.qps",
            "queue_wait_ms.p95"} <= names
    assert not {"l1_device_ms.qps", "block_scan_roofline.qps"} & names
    assert 0 < res["metrics"]["batch_fill.qps"]["value"] <= 100


def test_cell_from_new_files_only(monkeypatch, tmp_path):
    """A configuration, a traffic mix and a per-layer metric that exist
    only as new files load and run through the unchanged harness."""
    reader = ("def read(run):\n"
              "    return float(len(run.window.completed))\n")
    cfg = tiny.tiny_config()
    cfg["name"] = "fixture"
    root = tiny.make_root(tmp_path, dict(tiny.CLOSED, outstanding=4),
                          cfg=cfg, extra_metrics={"answers_seen": reader})
    cell = spec.load_cell("tiny.t", root)
    assert cell.config["name"] == "fixture" and cell.traffic["outstanding"] == 4
    assert spec.metric_reader_path("answers_seen.qps", root).exists()
    res = _run(monkeypatch, root, True)
    assert res["metrics"]["answers_seen"]["value"] == res["attempted"]


def test_no_tpu_exits_before_setup(capsys):
    from bench import run

    assert run.main(["--workload", "ws1m.backlog", "--seed", "1",
                     "--seconds", "1", "--trace", "0"]) == 2
    out, err = capsys.readouterr()
    assert out == "" and "no TPU" in err


def test_unknown_workload_is_an_error():
    with pytest.raises(KeyError):
        spec.load_cell("no.such.cell")


def test_host_cpu_reading_is_shares_of_core_time():
    from bench.device import HostCpu

    cpu = HostCpu()
    sum(i * i for i in range(200000))
    r = cpu.reading()
    assert r["process_cpu_s"] > 0
    if "busy_share" in r:
        assert 0.0 <= r["steal_share"] <= r["busy_share"] <= 1.0
