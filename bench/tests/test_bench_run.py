"""Whole runs of the harness on the CPU at a small scale.

The harness's look for a chip is skipped (``require_tpu=False``); the rest
of a run is driven as on the chip: the engine or solver, the traffic, the
float64 check and the result.  A sound run comes out correct; the bfloat16
control put in the program's place, and each fault a cell can have planted
in the timed path, come out not correct.
"""
import json
import os
import shutil
import subprocess
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from bench.lib import reference, registry
from bench.lib.harness import run_cell
from repro.runtime import solver as solver_mod
from repro.runtime.engine import SparseEngine
from repro.runtime.solver import SolverResult, SparseSolver

SCALE = 0.005
SERVE = ["ldoor.serve_open", "webbase-1M.serve_saturated", "ldoor.spmv_single"]


def _run(tmp_path, workload, seed=2**31 + 9, seconds=1.0, trace=False):
    result, _ = run_cell(workload, seed, seconds, trace, require_tpu=False,
                         scale=SCALE, cache_dir=tmp_path, log=lambda m: None)
    return result


@pytest.mark.parametrize("workload", SERVE + ["ldoor.cg"])
def test_sound_run_is_correct(tmp_path, workload):
    result = _run(tmp_path, workload)
    assert result["correct"], result["checks"]
    assert result["failed"] == 0 and result["attempted"] > 0
    assert list(result)[-1] == "checks"
    e2e, _ = registry.cell_metrics(registry.load_benchmark(), workload)
    assert set(result["metrics"]) == {m["name"] for m in e2e}
    assert result["device"]["platform"] == "cpu"


def test_traced_run_reports_layer_metrics(tmp_path):
    result = _run(tmp_path, "webbase-1M.serve_saturated", trace=True)
    assert result["correct"]
    # No device plane on the CPU: only the engine's counter is readable.
    assert set(result["metrics"]) == {"engine.padded_share"}
    assert result["device"]["window_s"] > 0 and "breakdown" in result


def _patch_launch(monkeypatch, alter):
    orig = SparseEngine._launch

    def launch(self, bucket, reqs):
        ys, ok = orig(self, bucket, reqs)
        return alter(self, ys, reqs), ok

    monkeypatch.setattr(SparseEngine, "_launch", launch)


def _answer_altered(self, ys, reqs):
    return ys.at[0].add(1.0)


def _half_batch_left_out(self, ys, reqs):
    if ys.ndim == 1:
        return jnp.zeros_like(ys)
    keep = jnp.arange(ys.shape[1]) < len(reqs) // 2
    return jnp.where(keep[None, :], ys, 0.0)


def _bf16_control(self, ys, reqs):
    xs = np.stack([np.asarray(r.x) for r in reqs])
    y = reference.control_spmv(self.a, xs).T
    if ys.ndim == 1:
        return jnp.asarray(y[:, 0])
    return jnp.asarray(np.pad(y, ((0, 0), (0, ys.shape[1] - y.shape[1]))))


@pytest.mark.parametrize("fault", [_answer_altered, _half_batch_left_out, _bf16_control])
@pytest.mark.parametrize("workload", SERVE)
def test_broken_serving_path_is_not_correct(tmp_path, monkeypatch, workload, fault):
    _patch_launch(monkeypatch, fault)
    result = _run(tmp_path, workload)
    assert not result["correct"], result["checks"]
    assert result["checks"]["spmv_err"]["value"] > reference.SPMV_ERR_LIMIT


def test_cg_step_that_returns_its_state_unchanged_is_not_correct(tmp_path, monkeypatch):
    def stuck_body(run, dot):
        def body(state):
            x, r, p, rs, it = state
            return (x, r, p, rs, it + 1)
        return body

    monkeypatch.setattr(solver_mod, "_cg_body", stuck_body)
    result = _run(tmp_path, "ldoor.cg", seconds=0.2)
    assert not result["correct"]
    assert result["checks"]["cg_residual"]["value"] > 0.5


def test_cg_answer_altered_is_not_correct(tmp_path, monkeypatch):
    orig = SparseSolver.cg

    def cg(self, b, **kw):
        res = orig(self, b, **kw)
        res.x = res.x.at[0].add(1.0)
        return res

    monkeypatch.setattr(SparseSolver, "cg", cg)
    assert not _run(tmp_path, "ldoor.cg", seconds=0.2)["correct"]


def test_bf16_control_in_place_of_the_solver_is_not_correct(tmp_path, monkeypatch):
    def cg(self, b, *, tol=1e-5, maxiter=500, x0=None):
        x = reference.control_cg(self.a, np.asarray(b)[None], tol, maxiter)[0]
        return SolverResult("cg", 1, 0.0, True, x=jnp.asarray(x))

    monkeypatch.setattr(SparseSolver, "cg", cg)
    result = _run(tmp_path, "ldoor.cg", seconds=0.2)
    assert not result["correct"]
    assert result["checks"]["cg_residual"]["value"] > reference.CG_RESIDUAL_LIMIT


def _cli(cwd, *args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "ldoor.serve_open",
         "--seed", str(2**31 + 1), "--seconds", "1", "--trace", "0", *args],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_cli_without_a_tpu_exits_nonzero_with_no_result():
    out = _cli(registry.ROOT)
    assert out.returncode != 0
    assert "no TPU" in out.stderr or "TPU" in out.stderr
    assert not any(line.startswith("{") for line in out.stdout.splitlines())


def test_cli_without_the_program_exits_nonzero(tmp_path):
    shutil.copy(registry.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(registry.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    out = _cli(tmp_path)
    assert out.returncode != 0
    assert not any(line.startswith("{") for line in out.stdout.splitlines())
