"""A tiny cell for CPU tests, laid out as a checkout of its own: a
``BENCHMARK.json`` and ``bench/`` files under a temporary root, with the
repository's metric readers copied in."""
from __future__ import annotations

import contextlib
import json
import shutil
from pathlib import Path

from bench import spec, sut  # noqa: F401  (sut puts src on sys.path)

STAMP = {"platform": "cpu", "kind": "TPU v5 lite", "count": 1}


def tiny_config() -> dict:
    cfg = json.loads((spec.ROOT / "bench" / "configs" /
                      "websearch-rl.shard256k.json").read_text())
    cfg["name"] = "tiny"
    cfg["n_blocks"] = 8
    cfg["widths"].update(block_docs=256, p_bins=64)
    cfg["engine"].update(min_bucket=4, max_bucket=8)
    cfg["querylog"]["n_queries"] = 600
    return cfg


def make_root(tmp: Path, traffic: dict, cfg: dict = None,
              extra_metrics: dict = None) -> Path:
    """A checkout under ``tmp`` holding one cell, ``tiny.t``."""
    (tmp / "bench" / "configs").mkdir(parents=True)
    (tmp / "bench" / "traffic").mkdir()
    shutil.copytree(spec.ROOT / "bench" / "metrics", tmp / "bench" / "metrics")
    for name, source in (extra_metrics or {}).items():
        (tmp / "bench" / "metrics" / f"{name}.py").write_text(source)
    (tmp / "bench" / "configs" / "tiny.json").write_text(
        json.dumps(cfg or tiny_config()))
    (tmp / "bench" / "traffic" / "t.json").write_text(json.dumps(traffic))
    b = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    b["configs"] = [{"name": "tiny", "source": "https://arxiv.org/abs/1804.04410",
                     "file": "bench/configs/tiny.json", "reduced": [],
                     "why": "CPU test size"}]
    b["workloads"] = [{"name": "tiny.t", "config": "tiny", "traffic": "t",
                       "chips": 1, "why": "CPU test size"}]
    for m in b["end_to_end"] + b["per_layer"]:
        m.pop("workloads", None)
    b["per_layer"] = [m for m in b["per_layer"]
                      if not m["name"].endswith(".p95")
                      or m["name"].startswith("queue_wait_ms")]
    b["per_layer"] += [{"name": n, "unit": "1", "better": "higher",
                        "source": "program_counter", "layer": "test",
                        "moves": "qps"} for n in (extra_metrics or {})]
    (tmp / "BENCHMARK.json").write_text(json.dumps(b))
    return tmp


CLOSED = {"kind": "closed", "outstanding": 8, "settle_s": 0.02,
          "select": "unique", "drain_s": 30}
OPEN = {"kind": "open", "arrivals": "poisson", "rate_qps": 20.0,
        "select": "unique", "drain_s": 30}


@contextlib.contextmanager
def cpu_harness(monkeypatch, root: Path):
    """Run the harness on the CPU: metric readers from ``root``, and no
    persistent compile cache (the run must not write into the checkout
    nor change JAX's settings for later tests)."""
    import jax

    import bench.run as br
    import repro.compile_cache as cc

    monkeypatch.setattr(br, "load_readers",
                        lambda ms: spec.load_readers(ms, root))
    monkeypatch.setattr(cc, "enable_compile_cache", lambda: "disabled")
    saved = {k: getattr(jax.config, k) for k in (
        "jax_enable_compilation_cache", "jax_default_matmul_precision",
        "jax_persistent_cache_min_compile_time_secs")}
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield br
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
