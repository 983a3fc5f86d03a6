"""Spans, program names and the compile counter.

The engine and the solver mark their steps with ``jax.profiler``
annotations (``engine.*``, ``solver.*``) that land in the same trace as
the device's programs; every program they launch is named
``jit_apply_<name>``; ``compile_counts`` counts jit cache misses and
backend compiles for the whole process.
"""
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.core import csr_from_dense, spd_shift
from repro.runtime.engine import SparseEngine
from repro.runtime.executable import (
    compile_counts,
    fused_batch_executable,
    hoisted_jit,
)
from repro.runtime.solver import SparseSolver
from repro.tune import PlanCache


def small(seed=0, m=96, density=0.06):
    rng = np.random.default_rng(seed)
    d = ((rng.random((m, m)) < density) * rng.standard_normal((m, m))).astype(
        np.float32
    )
    return csr_from_dense(d)


def xs_for(a, count, seed=1):
    rng = np.random.default_rng(seed)
    return [
        jnp.asarray(rng.standard_normal(a.shape[1]).astype(np.float32))
        for _ in range(count)
    ]


def traced(trace_dir: Path, body) -> list:
    """Run ``body`` under the profiler (host annotations only) and return
    its spans as ``(line, name, start_ns, end_ns, stats)``."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    pb = sorted(trace_dir.rglob("*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(str(pb)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("engine.", "solver.")):
                    out.append((line.name, e.name, e.start_ns,
                                e.start_ns + e.duration_ns, dict(e.stats)))
    return out


def inside(inner, outer) -> bool:
    return (inner[0] == outer[0] and outer[2] <= inner[2]
            and inner[3] <= outer[3])


def test_engine_spans_recorded_under_the_profiler(tmp_path):
    a = small(seed=1)
    eng = SparseEngine(a, ks=(1, 4), cache=PlanCache(), warmup=0, timed=1)
    xs = xs_for(a, 6)
    eng.run(xs[:4])  # every bucket's program compiled before the trace
    eng.run(xs[:1])

    def serve():
        reqs = [eng.submit(x) for x in xs[:5]]
        eng.drain()
        reqs[0].result()
        eng.submit(xs[5]).result()  # one caller: submit, then result

    spans = traced(tmp_path, serve)
    names = {s[1] for s in spans}
    assert {"engine.submit", "engine.step", "engine.launch", "engine.retire",
            "engine.device_wait", "engine.result"} <= names
    steps = [s for s in spans if s[1] == "engine.step"]
    launches = [s for s in spans if s[1] == "engine.launch"]
    retires = [s for s in spans if s[1] == "engine.retire"]
    waits = [s for s in spans if s[1] == "engine.device_wait"]
    assert len(launches) == 3  # 4 + 1 from the burst, then the lone caller
    assert all(any(inside(la, st) for st in steps) for la in launches)
    assert all(any(inside(w, r) for r in retires) for w in waits)
    # A launch and its retirement carry the same batch number.
    assert sorted(s[4]["batch"] for s in launches) == sorted(
        s[4]["batch"] for s in retires)
    assert len({s[4]["batch"] for s in launches}) == 3
    assert sorted(s[4]["take"] for s in launches) == [1, 1, 4]
    assert {s[4]["rid"] for s in spans if s[1] == "engine.submit"} == set(
        range(eng._rid - 6, eng._rid))
    eng.close()


def test_solver_spans_recorded_under_the_profiler(tmp_path):
    rng = np.random.default_rng(2)
    d = ((rng.random((120, 120)) < 0.04) * rng.standard_normal((120, 120)))
    a = spd_shift(csr_from_dense(d.astype(np.float32)))
    s = SparseSolver(a, cache=PlanCache(), warmup=0, timed=1)
    b = jnp.asarray(rng.standard_normal(120).astype(np.float32))
    s.cg(b, tol=1e-5)  # compiled before the trace
    res = []
    spans = traced(tmp_path, lambda: res.append(s.cg(b, tol=1e-5)))
    assert res[0].converged
    names = [sp[1] for sp in spans]
    assert sorted(set(names)) == ["solver.call", "solver.device_wait",
                                  "solver.fetch", "solver.launch"]
    (call,) = [sp for sp in spans if sp[1] == "solver.call"]
    assert call[4] == {"solver": "cg", "k": 1}
    for sp in spans:
        if sp[1] in ("solver.launch", "solver.device_wait"):
            assert inside(sp, call)
    (fetch,) = [sp for sp in spans if sp[1] == "solver.fetch"]
    assert fetch[2] >= call[3]  # the host reads the result after the call


def _module_name(lowered) -> str:
    head = lowered.as_text().split("\n", 1)[0]
    return head.split("@", 1)[1].split()[0]


@pytest.mark.parametrize("name, module", [
    ("engine_k16", "jit_apply_engine_k16"),
    (None, "jit_apply"),
])
def test_fused_batch_executable_names_its_module(name, module):
    n = 32
    w = jnp.arange(n, dtype=jnp.float32)
    prog = fused_batch_executable(lambda slab: slab * w[:, None], bucket=16,
                                  name=name)
    xs = [jnp.ones(n, jnp.float32)] * 16
    assert _module_name(prog.lower(*xs)) == module
    np.testing.assert_allclose(np.asarray(prog(*xs)),
                               np.tile(np.asarray(w)[:, None], (1, 16)))


def test_program_names_keep_the_jit_apply_prefix():
    with pytest.raises(ValueError, match="letters, digits"):
        hoisted_jit(lambda x: x, name="engine/k16")
    a = small(seed=3)
    eng = SparseEngine(a, ks=(1, 4), cache=PlanCache(), warmup=0, timed=1)
    xs = xs_for(a, 4)
    for k in eng.ks:
        low = eng._exec(k).lower(*xs[:k])
        assert _module_name(low) == f"jit_apply_engine_k{k}"
    s = SparseSolver(spd_shift(a), cache=PlanCache(), warmup=0, timed=1)
    s.cg(xs[0], tol=1e-5)
    (prog,) = s._progs.values()
    low = prog.lower(xs[0], jnp.zeros_like(xs[0]), jnp.float32(1e-5))
    assert _module_name(low) == "jit_apply_solver_cg"
    eng.close()


def test_compile_counter_rises_on_a_new_shape_and_stays_flat_when_warm():
    w = jnp.float32(3.0)
    prog = hoisted_jit(lambda x: x * w + 1.0, name="counter_probe")
    before = compile_counts()
    prog(jnp.ones(8, jnp.float32)).block_until_ready()
    cold = compile_counts()
    assert cold["jaxpr_traces"] > before["jaxpr_traces"]
    assert cold["backend_compiles"] > before["backend_compiles"]
    assert cold["backend_compile_s"] > before["backend_compile_s"]
    assert (cold["programs"].get("jit(apply_counter_probe)", 0)
            == before["programs"].get("jit(apply_counter_probe)", 0) + 1)
    prog(jnp.zeros(8, jnp.float32)).block_until_ready()
    warm = compile_counts()
    for key in ("jaxpr_traces", "backend_compiles"):
        assert warm[key] == cold[key]
    prog(jnp.ones(16, jnp.float32)).block_until_ready()
    assert compile_counts()["backend_compiles"] > warm["backend_compiles"]
