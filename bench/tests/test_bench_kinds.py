"""A new kind of cell arrives as new files only.

In a copy of ``bench/``, a configuration whose pattern family comes from
``bench/families``, a mix that names a new ``request``, the kind module it
names and a per-layer reader that reads ``RunRecord.spans`` are added,
with their entries in ``BENCHMARK.json``, and no file that was there is
edited.  The cell then runs end to end on the CPU (the look for a chip
skipped) through ``run_cell`` and through ``bench/run.py``.  A request or
a family with no file fails at once, naming the file looked for.
"""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench.lib import registry
from bench.lib.harness import build_cell, run_cell

CELL = "toy-grid.fixed_cg"
SEED = 2**31 + 21

FAMILY = '''"""A 3-D 7-point stencil on a cube of side t["side"]."""
import numpy as np


def pattern(t, scale, rng):
    s = max(int(round(t["side"] * scale ** (1 / 3))), 2)
    idx = np.arange(s ** 3)
    i, j, k = idx // (s * s), (idx // s) % s, idx % s
    rows, cols = [idx], [idx]
    for di, dj, dk in ((1, 0, 0), (-1, 0, 0), (0, 1, 0), (0, -1, 0),
                       (0, 0, 1), (0, 0, -1)):
        ii, jj, kk = i + di, j + dj, k + dk
        ok = (ii >= 0) & (ii < s) & (jj >= 0) & (jj < s) & (kk >= 0) & (kk < s)
        rows.append(idx[ok])
        cols.append(((ii * s + jj) * s + kk)[ok])
    return s ** 3, np.concatenate(rows), np.concatenate(cols)
'''

KIND = '''"""A fixed budget of CG iterations a solve, with no convergence test, on
a stencil with diagonal 6.5 and neighbours -1."""
import time

import numpy as np

from bench.lib import reference, traffic
from bench.lib.harness import (Reduced, compared, device_vectors, host_stack,
                               program_csr)
from bench.lib.suite import Csr
from repro.runtime.solver import SparseSolver


def build(setup):
    t = time.perf_counter()
    store, a = setup.matrix()
    diag = np.repeat(np.arange(a.shape[0]), np.diff(a.indptr)) == a.indices
    a = Csr(a.shape, a.indptr, a.indices,
            np.where(diag, 6.5, -1.0).astype(np.float32))
    setup.log_matrix(store, a, t)
    t = time.perf_counter()
    system = SparseSolver(program_csr(a), cache=setup.plan_cache())
    pool = device_vectors(setup.seed, 2, int(setup.mix["b_pool"]), a.shape[0])
    setup.log_plans({1: system.op(1)}, t)
    system.cg(pool[0], tol=-1.0, maxiter=int(setup.mix["iterations"]))
    return system, {1: system.op(1)}, pool, a


def drive(cell, seconds, seed):
    return traffic.cg_closed(cell.system, cell.pool, cell.mix, seconds, seed,
                             -1.0, int(cell.mix["iterations"]))


def reduce(cell, out, log):
    idx = sorted(out.xs)
    xs = host_stack(out.xs[i] for i in idx)
    e2e = {"toy_solve_s": out.window_s / out.solves}
    return Reduced(e2e, {"cg": {"iterations": list(out.iterations)}},
                   out.solves + out.failed, out.failed,
                   (host_stack(cell.pool)[idx], xs))


def close(cell):
    pass


def check(cell, a64, red, log):
    res = reference.cg_residuals(a64, *red.sample)
    return {"toy_residual": compared(res.max(), 1e-4),
            "toy_failed": compared(red.failed, 0)}
'''

READER = '''"""toy.solver_host_ms: the solver's host time per solve, from the spans."""
from bench.lib.spans import solver_host_ms


def read(run):
    return None if run.spans is None else solver_host_ms(run.spans)
'''


def _copy_bench(root):
    shutil.copy(registry.ROOT / "BENCHMARK.json", root)
    shutil.copytree(registry.BENCH_DIR, root / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    (root / "src").symlink_to(registry.ROOT / "src")


def _add(root, path, text):
    path = root / "bench" / path
    assert not path.exists()
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(text)


def _add_entries(root, **entries):
    bm = json.loads((root / "BENCHMARK.json").read_text())
    for key, entry in entries.items():
        bm[key].append(entry)
    (root / "BENCHMARK.json").write_text(json.dumps(bm))


@pytest.fixture
def toy(tmp_path):
    """A copy of the benchmark with the toy kind added as new files only."""
    _copy_bench(tmp_path)
    side = 8
    cfg = {"name": "toy-grid", "n_rows": side ** 3,
           "nnz": side ** 3 + 6 * side * side * (side - 1), "max_row": 7,
           "generator": {"idx": 0, "family": "grid7", "side": side},
           "scale": 1.0, "structure_seed": 0, "reduced": []}
    _add(tmp_path, "families/grid7.py", FAMILY)
    _add(tmp_path, "configs/toy-grid.json", json.dumps(cfg))
    _add(tmp_path, "traffic/fixed_cg.json", json.dumps(
        {"loop": "closed", "request": "fixed_cg", "b_pool": 8, "iterations": 50}))
    _add(tmp_path, "kinds/fixed_cg.py", KIND)
    _add(tmp_path, "metrics/toy.solver_host_ms.py", READER)
    _add_entries(
        tmp_path,
        configs={"name": "toy-grid", "source": "a toy", "reduced": [],
                 "file": "bench/configs/toy-grid.json", "why": "a toy"},
        workloads={"name": CELL, "config": "toy-grid", "traffic": "fixed_cg",
                   "chips": 1, "why": "a toy"},
        end_to_end={"name": "toy_solve_s", "unit": "s", "better": "lower",
                    "bound": 0.01, "source": "host_clock", "workloads": [CELL]},
        per_layer={"name": "toy.solver_host_ms", "unit": "ms", "better": "lower",
                   "source": "program_span", "layer": "solver",
                   "moves": "toy_solve_s", "workloads": [CELL]})
    for old in registry.BENCH_DIR.rglob("*"):  # nothing that was there changed
        rel = old.relative_to(registry.BENCH_DIR)
        if old.is_file() and not {".cache", "__pycache__"} & set(rel.parts):
            assert (tmp_path / "bench" / rel).read_bytes() == old.read_bytes(), rel
    return tmp_path


def _assert_sound(result, trace):
    assert result["correct"], result["checks"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert list(result)[-1] == "checks"
    assert result["checks"]["toy_residual"]["value"] < 1e-4
    if trace:  # the reader found the solver's spans
        assert result["metrics"]["toy.solver_host_ms"]["value"] > 0
    else:
        assert set(result["metrics"]) == {"toy_solve_s", "setup_s"}


@pytest.mark.parametrize("trace", [False, True])
def test_new_kind_runs_through_run_cell(toy, trace):
    result, _ = run_cell(CELL, SEED, 0.5, trace, bench_dir=toy / "bench",
                         require_tpu=False, cache_dir=toy / "cache",
                         log=lambda m: None)
    _assert_sound(result, trace)


# bench/run.py as the benchmark runs it, but with the look for a chip
# skipped, so that the rest of the run goes as on one.
NO_CHIP = """import sys
sys.path[:0] = ['.', 'src']
from bench.lib import harness
info = harness.device_info
harness.device_info = lambda chips, require_tpu: info(chips, False)
from bench import run
sys.exit(run.main(sys.argv[1:]))
"""


@pytest.mark.parametrize("trace", [0, 1])
def test_new_kind_runs_through_bench_run(toy, trace):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(toy / "jax_cache"))
    env.pop("PYTHONPATH", None)
    out = subprocess.run(
        [sys.executable, "-c", NO_CHIP, "--workload", CELL, "--seed", str(SEED),
         "--seconds", "0.5", "--trace", str(trace)],
        cwd=toy, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    _assert_sound(json.loads(out.stdout.splitlines()[-1]), trace)
    assert out.stderr.splitlines()[-2].startswith("check toy_residual ")


def _build(root):
    return build_cell(CELL, SEED, bench_dir=root / "bench", require_tpu=False,
                      cache_dir=root / "cache", log=lambda m: None)


def test_request_with_no_kind_file_fails_naming_it(toy):
    (toy / "bench" / "kinds" / "fixed_cg.py").unlink()
    with pytest.raises(FileNotFoundError, match="bench/kinds/fixed_cg.py"):
        _build(toy)


def test_family_with_no_file_fails_naming_it(toy):
    (toy / "bench" / "families" / "grid7.py").unlink()
    with pytest.raises(FileNotFoundError, match="bench/families/grid7.py"):
        _build(toy)
