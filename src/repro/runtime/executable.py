"""Persistent compiled dispatch + on-device batch assembly for serving.

The paper's SpMV kernels are latency-bound; after the kernel layer hides its
own latency (PR 4), what remains on the serving hot path is *host* latency:
per-call tracing-cache lookups, Python-side RHS stacking, a fresh output
allocation per batch, and a mandatory block between batches.  This module
removes it:

* :func:`hoisted_jit` — ``jax.jit`` for functions that close over prepared
  device arrays.  A plain ``jax.jit`` writes every closed-over array into
  the program as an HLO constant (a 4 MB array becomes 8 MB of HLO text),
  so a whole Table-1 matrix would be baked into every k-bucket program and
  copied through the compiler.  ``hoisted_jit`` traces once per argument
  signature, lifts the closed-over arrays out of the jaxpr, and passes them
  to the compiled program as device-resident arguments.  The one rule
  behind it: no jitted function closes over a prepared array.  Building
  blocks (``core.spmv._csr_apply``, the kernels' jitted entry points, the
  mesh runner's program) take their arrays as arguments; whatever composes
  them through closures — runners, bucket and solver programs — is hoisted.
  Its cost on a warmed dispatch, measured on a CPU host at k = 16: about
  4 us for the argument-signature lookup in Python, and about 3 us for
  handing the arrays over, against a program with the arrays baked in.

  ``name=`` labels the program: the jitted function is ``apply_<name>``,
  so its module (the profiler's "XLA Modules" line, the HLO dump, the
  compile log) is ``jit_apply_<name>`` and each bucket or solver program
  can be told apart from the others.

* :func:`compile_counts` — how many jit cache misses (jaxpr traces) and
  backend compiles the process has run, with their seconds and the
  compiled programs' names, from one ``jax.monitoring`` listener
  registered at import.  A caller differences two snapshots to learn
  whether a window of serving recompiled anything, and what.

* :func:`aot_compile` — lower a function ONCE to an explicitly AOT-compiled
  executable over given shapes (used by ``SparseOperator.aot`` and the
  benchmarks' kernel-only baselines), with the same argument hoisting.

* :func:`fused_batch_executable` — ONE persistent compiled program per
  k-bucket that does everything a dispatch needs: assemble the batch's
  (already device-resident) request vectors into the bucket's RHS slab *on
  device* and invoke the bucket's tuned kernel in the same launch.  Burst
  tails reuse the same program — the engine pads the argument list with
  its preallocated zero column, bit-identical to the synchronous path's
  zero-column padding, so a novel occupancy never recompiles.  A
  steady-state batch costs exactly one launch: the same count as the bare
  kernel, where the legacy path (``SparseEngine(legacy_dispatch=True)``)
  pays a list flatten + eager stack + block per batch.

Dispatch-path donation note: the issue's design donates the stacked-RHS
buffer to the dispatch.  Measured on this jax (0.4.37) CPU backend,
``donate_argnums`` disqualifies a call from the C++ jit dispatch fastpath —
+70..100us per call of Python argument processing, several times the entire
overhead budget this module exists to remove — and XLA CPU additionally
rewrites whole donated buffers on dynamic-index updates.  So the per-batch
dispatch path deliberately does NOT donate; donation is kept where a buffer
genuinely wants in-place reuse off the per-call fastpath:
``SparseOperator.aot(donate_rhs=True)`` (opt-in persistent executables) and
the mesh runner's engine-owned RHS slabs (``runner(..., donate_rhs=True)``).

The executables returned here are persistent ``jax.jit`` programs rather
than ``.lower().compile()`` objects: both lower exactly once, but a warmed
jit call takes the C++ fastpath, which measures ~20us/call cheaper than
``Compiled.__call__``'s Python path on CPU — at serving rates that is the
difference ``benchmarks/fig15_dispatch.py`` exists to count.
"""
from __future__ import annotations

import re
import threading
import warnings
from collections import Counter
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["aot_compile", "compile_counts", "fused_batch_executable",
           "finite_guard", "hoisted_jit"]

# jax.monitoring duration events: a jit cache miss traces a jaxpr; a
# program the process does not hold yet goes through the backend (an XLA
# compile, or a load from the persistent compilation cache).
JAXPR_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

_counts_lock = threading.Lock()
_counts = {"jaxpr_traces": 0, "jaxpr_trace_s": 0.0,
           "backend_compiles": 0, "backend_compile_s": 0.0}
_compiled_programs: Counter = Counter()


def _count_compile(event: str, duration: float, **kwargs) -> None:
    if event == JAXPR_TRACE_EVENT:
        with _counts_lock:
            _counts["jaxpr_traces"] += 1
            _counts["jaxpr_trace_s"] += duration
    elif event == BACKEND_COMPILE_EVENT:
        with _counts_lock:
            _counts["backend_compiles"] += 1
            _counts["backend_compile_s"] += duration
            _compiled_programs[str(kwargs.get("fun_name", "?"))] += 1


jax.monitoring.register_event_duration_secs_listener(_count_compile)


def compile_counts() -> dict:
    """A snapshot of the process's compile counter.

    ``jaxpr_traces``/``jaxpr_trace_s``: jit cache misses and the seconds
    spent tracing them; ``backend_compiles``/``backend_compile_s``:
    programs handed to the backend (compiled, or loaded from the
    persistent cache); ``programs``: backend compiles by the name jax
    gives the program (``jit(apply_engine_k16)`` for the module
    ``jit_apply_engine_k16``).
    Difference two snapshots to count what a window compiled; a warm
    serving loop compiles nothing.
    """
    with _counts_lock:
        return {**_counts, "programs": dict(_compiled_programs)}


def _signature(xs) -> tuple:
    try:  # flat array arguments (jax or numpy): the per-call fast path
        return tuple([(x.shape, x.dtype) for x in xs])
    except AttributeError:  # pytrees / python scalars
        leaves, tree = jax.tree.flatten(xs)
        return tree, tuple((np.shape(x), np.result_type(x)) for x in leaves)


def _hoist(fn: Callable, xs) -> tuple[Callable, list]:
    """Trace ``fn`` on ``xs``; return ``(apply(consts, *xs), consts)``.

    Building blocks must take their arrays as jit *arguments* (not close
    over them inside their own ``jax.jit``): only then do the arrays reach
    this trace as top-level constants that can be lifted out.
    """
    closed, out_shape = jax.make_jaxpr(fn, return_shape=True)(*xs)
    tree = jax.tree.structure(out_shape)
    jaxpr = closed.jaxpr
    # The first call may come from inside an outer trace (a fused solver
    # program calling its matvec): the cached constants must still be
    # concrete device arrays, never that trace's tracers.
    with jax.ensure_compile_time_eval():
        consts = [
            c if isinstance(c, jax.Array) else jax.device_put(c)
            for c in closed.consts
        ]

    def apply(cs, *args):
        outs = jax.core.eval_jaxpr(jaxpr, cs, *jax.tree.leaves(args))
        return jax.tree.unflatten(tree, outs)

    return apply, consts


_NAME = re.compile(r"[A-Za-z0-9_]+")


def hoisted_jit(fn: Callable, *, name: str | None = None,
                donate_argnums=()) -> Callable:
    """``jax.jit(fn)`` with closed-over arrays passed as arguments.

    Compiles once per positional-argument signature (shapes and dtypes);
    the prepared arrays ``fn`` closes over stay where they live on device
    and are handed to the program on every call, never copied into it.
    ``name`` (letters, digits, ``_``) names the program ``jit_apply_<name>``
    instead of ``jit_apply``.
    """
    if name is not None and not _NAME.fullmatch(name):
        raise ValueError(f"program name {name!r} must be letters, digits or _")
    progs: dict = {}
    donate = tuple(int(i) + 1 for i in donate_argnums)

    def entry(xs):
        key = _signature(xs)
        e = progs.get(key)
        if e is None:
            apply, consts = _hoist(fn, xs)
            if name is not None:
                apply.__name__ = apply.__qualname__ = f"apply_{name}"
            e = progs[key] = (jax.jit(apply, donate_argnums=donate), consts)
        return e

    def call(*xs):
        prog, consts = entry(xs)
        return prog(consts, *xs)

    def lower(*xs):
        """The ``jax.stages.Lowered`` program for these arguments."""
        prog, consts = entry(xs)
        return prog.lower(consts, *xs)

    call.lower = lower
    return call


def aot_compile(fn: Callable, *avals, donate_argnums=()) -> Callable:
    """Lower ``fn`` once over ``avals`` and return the compiled executable.

    The returned callable accepts exactly the lowered shapes/dtypes and
    never touches the jit tracing cache.  Closure-captured jax arrays are
    hoisted into device-resident arguments (see :func:`hoisted_jit`).
    Prefer this for eager, shape-explicit lowering (operator pins,
    benchmark baselines); the serving engine's own executables use warmed
    jit programs instead (see module docstring).
    """
    apply, consts = _hoist(fn, avals)
    with warnings.catch_warnings():
        # Donation is best-effort by contract here: when XLA finds no
        # output/scratch to alias a donated operand with, it ignores the
        # donation.  Scoped to this lowering — never a process-global
        # filter that would swallow the diagnostic for user code.
        warnings.filterwarnings(
            "ignore", message="Some donated buffers were not usable"
        )
        compiled = (
            jax.jit(apply,
                    donate_argnums=tuple(int(i) + 1 for i in donate_argnums))
            .lower(consts, *avals)
            .compile()
        )
    return lambda *xs: compiled(consts, *xs)


def finite_guard(fn: Callable) -> Callable:
    """Wrap an executable so every call returns ``(ys, all_finite)``.

    The reduction runs ON DEVICE (one jitted ``isfinite().all()``), so the
    guard costs a scalar transfer at retirement, never a slab transfer.
    Used by the engine's non-fused paths (mesh assembly composition,
    sparse-RHS runners); the fused bucket programs bake the same check in
    via ``fused_batch_executable(..., guard=True)`` instead.
    """
    check = jax.jit(lambda ys: jnp.isfinite(ys).all())

    def guarded(*xs):
        ys = fn(*xs)
        return ys, check(ys)

    return guarded


def fused_batch_executable(
    run: Callable | None, *, bucket: int, guard: bool = False,
    name: str | None = None,
) -> Callable:
    """Persistent compiled ``(x_0..x_{bucket-1}) -> ys`` for one bucket.

    ``run`` is the bucket plan's bound runner (prepared arrays already
    bound).  Assembly happens inside the program, on device: the
    ``bucket`` argument vectors stack straight into the (n, bucket) operand
    slab — one fused op, no intermediate buffer — and the kernel consumes
    it in the same launch.  The runner's prepared arrays enter as
    arguments (:func:`hoisted_jit`), not as constants of the program.

    ONE executable serves every occupancy of the bucket: the engine pads a
    burst tail's argument list with its preallocated device-resident zero
    column, which is bit-identical to the synchronous path's zero-column
    padding and means a novel tail size never triggers a serving-time
    recompile (a per-occupancy specialization would re-lower the whole
    kernel for up to bucket-1 tail shapes).

    ``run=None`` returns the slab itself instead of applying a kernel (the
    mesh path feeds its shard_map runner, which places the slab across
    devices before its own jitted program runs).

    ``guard=True`` fuses an on-device ``isfinite().all()`` over the output
    into the same program — the call returns ``(ys, all_finite)`` and the
    engine's supervisor treats a False flag as a fault (NaN/Inf outputs
    from a poisoned operand or a broken kernel).  Opt-in: the extra
    reduction is device work the default hot path does not pay.

    ``name`` names the program (see :func:`hoisted_jit`): the engine's
    tuned bucket programs are ``jit_apply_engine_k<bucket>``.
    """
    if bucket == 1:

        def fn(x):
            ys = x[:, None] if run is None else run(x)
            return (ys, jnp.isfinite(ys).all()) if guard else ys

    else:

        def fn(*xs):
            slab = jnp.stack(xs, axis=1)  # (n, bucket)
            ys = slab if run is None else run(slab)
            return (ys, jnp.isfinite(ys).all()) if guard else ys

    return hoisted_jit(fn, name=name)
