"""The harness finds configurations, mixes and readers by name, and
BENCHMARK.json keeps to the benchmark's contract."""
import json
import re
from pathlib import Path

from bench.lib import registry

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def test_added_files_are_found_by_name(tmp_path):
    for sub in ("configs", "traffic", "metrics"):
        (tmp_path / sub).mkdir()
    (tmp_path / "configs" / "toy-graph.json").write_text(json.dumps({"name": "toy-graph"}))
    (tmp_path / "traffic" / "bursty.x2.json").write_text(
        json.dumps({"loop": "open", "request": "spmv", "rate_per_s": 3}))
    (tmp_path / "metrics" / "toy.share.py").write_text(
        "def read(run):\n    return None if run is None else 42.0\n")
    assert registry.load_config("toy-graph", tmp_path) == {"name": "toy-graph"}
    assert registry.load_traffic("bursty.x2", tmp_path)["rate_per_s"] == 3
    read = registry.load_reader("toy.share", tmp_path)
    assert read(object()) == 42.0 and read(None) is None


def test_cell_metrics_follow_workloads_and_moves():
    bm = {
        "end_to_end": [
            {"name": "a_ms", "workloads": ["c1"]},
            {"name": "b_rps", "workloads": ["c2"]},
            {"name": "setup_s"},
        ],
        "per_layer": [
            {"name": "x", "moves": "a_ms", "workloads": ["c1", "c2"]},
            {"name": "y", "moves": "b_rps"},
            {"name": "z", "moves": "setup_s"},
        ],
    }
    e2e, layer = registry.cell_metrics(bm, "c1")
    assert [m["name"] for m in e2e] == ["a_ms", "setup_s"]
    assert [m["name"] for m in layer] == ["x", "z"]
    e2e, layer = registry.cell_metrics(bm, "c2")
    assert [m["name"] for m in layer] == ["y", "z"]  # c2 reports no a_ms


def test_benchmark_json_keeps_to_the_contract():
    bm = registry.load_benchmark()
    assert set(bm) == {"command", "paths", "run_seconds", "configs", "workloads",
                       "end_to_end", "per_layer"}
    assert bm["paths"] == ["bench"]
    assert bm["command"][1] == "bench/run.py"
    assert isinstance(bm["run_seconds"], int) and 1 <= bm["run_seconds"] <= 51
    root = registry.ROOT
    config_names = set()
    for c in bm["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("bench/")
        assert c["file"] == f"bench/configs/{c['name']}.json"
        assert json.loads((root / c["file"]).read_text())["reduced"] == c["reduced"]
        config_names.add(c["name"])
    cells = set()
    for w in bm["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and w["config"] in config_names
        assert (root / "bench" / "traffic" / f"{w['traffic']}.json").exists()
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        cells.add(w["name"])
    assert config_names == {w["config"] for w in bm["workloads"]}
    for mix in (root / "bench" / "traffic").glob("*.json"):  # each names a kind
        kind = registry.load_kind(json.loads(mix.read_text())["request"])
        for hook in ("build", "drive", "reduce", "close", "check"):
            assert callable(getattr(kind, hook)), (mix.name, hook)
    e2e_names = set()
    for m in bm["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert UNIT.match(m["unit"]) and m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m.get("workloads", cells)) <= cells
        e2e_names.add(m["name"])
    assert "setup_s" in e2e_names
    for m in bm["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in e2e_names and set(m.get("workloads", cells)) <= cells
        assert (root / "bench" / "metrics" / f"{m['name']}.py").exists()
    for cell in cells:  # each cell reports setup_s, another e2e and a layer metric
        e2e, layer = registry.cell_metrics(bm, cell)
        assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2 and layer
    assert len(json.dumps(bm)) < 64 * 1024
    assert Path(root / "bench" / "run.py").exists()
