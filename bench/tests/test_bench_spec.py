"""BENCHMARK.json holds to its schema, and every name in it resolves to
a file under ``bench/``."""
import json
import re

from bench import spec

B = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
LINE = re.compile(r"^[^\n\t]{1,200}$")


def test_top_level_keys_and_command():
    assert list(B) == ["command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"]
    assert B["paths"] == ["bench"]
    assert B["command"][:3] == ["python3", "-m", "bench.run"]
    assert 1 <= B["run_seconds"] <= 51
    runs = 2 + 14 * 24
    assert runs * (B["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_configs_and_cells_resolve():
    configs = {c["name"]: c for c in B["configs"]}
    for c in B["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and LINE.match(c["why"])
        cfg = json.loads((spec.ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"] and cfg["reduced"] == c["reduced"]
        assert c["file"].startswith("bench/configs/")
    used = set()
    for w in B["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and LINE.match(w["why"])
        assert w["chips"] in (1, 4) and w["config"] in configs
        used.add(w["config"])
        cell = spec.load_cell(w["name"])
        assert cell.traffic["kind"] in ("closed", "open")
    assert used == set(configs)


def test_metrics():
    cells = {w["name"] for w in B["workloads"]}
    e2e = {m["name"] for m in B["end_to_end"]}
    assert "setup_s" in e2e
    for m in B["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert next(m for m in B["end_to_end"]
                if m["name"] == "setup_s")["bound"] == 0.25
    names = [m["name"] for m in B["end_to_end"] + B["per_layer"]]
    assert len(names) == len(set(names))
    for m in B["end_to_end"] + B["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells
    for m in B["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in e2e and LINE.match(m["layer"])
        assert spec.metric_reader_path(m["name"]).exists()
        for cell in m["workloads"]:
            moved = next(e for e in B["end_to_end"] if e["name"] == m["moves"])
            assert cell in moved.get("workloads", cells)
    for cell in cells:
        c = spec.load_cell(cell)
        assert any(m["name"] != "setup_s" for m in c.end_to_end)
        assert c.per_layer
    assert len(json.dumps(B)) <= 64 * 1024
