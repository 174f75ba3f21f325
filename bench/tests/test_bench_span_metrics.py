"""The per-layer metrics read from the program's spans inside the input
build and the executor: a whole traced run of a tiny cell on the CPU,
and the readers on a run without spans (an untraced run, or a program
without them)."""
import json
import types

import jax
import pytest

from bench import spec
from bench.run import RunData
from bench.tests import tiny

READERS = ("occupancy_host_ms", "h2d_mb", "device_wait_ms")
BUCKET = 8


def test_traced_run_reports_the_span_metrics(monkeypatch, tmp_path):
    cfg = tiny.tiny_config()
    cfg["engine"].update(min_bucket=BUCKET, max_bucket=BUCKET)
    root = tiny.make_root(tmp_path, tiny.CLOSED, cfg=cfg)
    lines = []
    with tiny.cpu_harness(monkeypatch, root) as br:
        res = br.run_cell(spec.load_cell("tiny.t", root), 2**31 + 23, 2.0,
                          True, tiny.STAMP, jax.devices()[:1],
                          out=lines.append, err=lambda s: None)
    assert json.loads(lines[-1])["correct"] is True
    got = {k: v["value"] for k, v in res["metrics"].items()}
    assert {f"{r}.qps" for r in READERS} <= set(got)
    assert 0 < got["occupancy_host_ms.qps"] <= got["inputs_host_ms.qps"]
    assert got["device_wait_ms.qps"] > 0
    # Occupancy (B x blocks x T x F x W words of 4 B), the term mask
    # (B x T bool) and idf (B x T float32), W = block_docs / 32.
    t, f = 4, 4
    words = cfg["widths"]["block_docs"] // 32
    want = BUCKET * (cfg["n_blocks"] * t * f * words * 4 + t + 4 * t)
    assert got["h2d_mb.qps"] == pytest.approx(want / 1e6, rel=1e-12)


@pytest.mark.parametrize("name", READERS)
def test_reader_finds_nothing_without_spans_or_counter(name):
    row = {"category": 0, "bucket": BUCKET, "n_real": BUCKET,
           "n_padded": 0, "t_inputs_s": 0.5}
    run = RunData(cell=spec.load_cell("ws1m.backlog"),
                  window=types.SimpleNamespace(t0=0.0),
                  batches=[row, row], spans=None, trace=None,
                  device_kind="TPU v5 lite")
    assert spec.load_reader(name)(run) is None
