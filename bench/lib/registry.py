"""Find a cell's configuration, traffic mix and per-layer readers by name.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric sits in a file of its own under the benchmark directory:

* ``configs/<config>.json``: the deployment (sizes, settings, source);
* ``traffic/<mix>.json``: the parameters the one generator in
  ``bench.lib.traffic`` reads;
* ``metrics/<metric>.py``: a reader with ``read(run) -> float | None``.

So a later change adds a configuration, a mix or a metric by adding a file
and an entry in ``BENCHMARK.json``, and edits no file that is there.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parent


def load_benchmark(root: Path = ROOT) -> dict:
    with open(Path(root) / "BENCHMARK.json") as f:
        return json.load(f)


def find_workload(benchmark: dict, name: str) -> dict:
    for w in benchmark["workloads"]:
        if w["name"] == name:
            return w
    names = ", ".join(w["name"] for w in benchmark["workloads"])
    raise KeyError(f"no workload {name!r} in BENCHMARK.json (have: {names})")


def _json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_config(name: str, bench_dir: Path = BENCH_DIR) -> dict:
    return _json(Path(bench_dir) / "configs" / f"{name}.json")


def load_traffic(name: str, bench_dir: Path = BENCH_DIR) -> dict:
    return _json(Path(bench_dir) / "traffic" / f"{name}.json")


def load_reader(name: str, bench_dir: Path = BENCH_DIR):
    """The ``read`` function of ``metrics/<name>.py``."""
    path = Path(bench_dir) / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(benchmark: dict, workload: str) -> tuple[list, list]:
    """The end-to-end and per-layer metric entries a cell reports.

    An entry with a ``workloads`` list applies to those cells; one without
    applies to every cell (a per-layer one, to every cell that reports the
    end-to-end metric it moves).
    """
    e2e = [m for m in benchmark["end_to_end"]
           if workload in m.get("workloads", [workload])]
    names = {m["name"] for m in e2e}
    layer = [m for m in benchmark["per_layer"]
             if workload in m.get("workloads", [workload]) and m["moves"] in names]
    return e2e, layer
