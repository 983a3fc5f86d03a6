"""Find a cell's configuration, traffic mix, kind, pattern family and
per-layer readers by name.

Everything that belongs to one configuration, one traffic mix, one kind of
cell or one per-layer metric sits in a file of its own under the benchmark
directory:

* ``configs/<config>.json``: the deployment (sizes, settings, source);
* ``traffic/<mix>.json``: the parameters the one generator in
  ``bench.lib.traffic`` reads; its ``request`` names the kind;
* ``kinds/<request>.py``: how a cell of that kind is built, driven,
  reduced and checked (the hooks are in ``bench/kinds/__init__.py``);
* ``families/<family>.py``: a pattern family that ``bench.lib.suite``
  does not have, ``pattern(t, scale, rng) -> (n, rows, cols)``;
* ``metrics/<metric>.py``: a reader with ``read(run) -> float | None``.

So a later change adds a configuration, a mix, a kind, a family or a
metric by adding a file and an entry in ``BENCHMARK.json``, and edits no
file that is there.
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


def load_module(sub: str, name: str, bench_dir: Path = BENCH_DIR):
    """The module in ``<bench_dir>/<sub>/<name>.py``; a missing file is an
    error that names the path looked for."""
    path = Path(bench_dir) / sub / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"{name!r} has no file: {path} does not exist")
    spec = importlib.util.spec_from_file_location(
        f"bench_{sub}_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str, bench_dir: Path = BENCH_DIR):
    """The ``read`` function of ``metrics/<name>.py``."""
    return load_module("metrics", name, bench_dir).read


def load_kind(request: str, bench_dir: Path = BENCH_DIR):
    """The kind module ``kinds/<request>.py`` that a mix's ``request`` names."""
    return load_module("kinds", request, bench_dir)


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
