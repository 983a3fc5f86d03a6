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

import warnings
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["aot_compile", "fused_batch_executable", "finite_guard",
           "hoisted_jit"]


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


def hoisted_jit(fn: Callable, *, donate_argnums=()) -> Callable:
    """``jax.jit(fn)`` with closed-over arrays passed as arguments.

    Compiles once per positional-argument signature (shapes and dtypes);
    the prepared arrays ``fn`` closes over stay where they live on device
    and are handed to the program on every call, never copied into it.
    """
    progs: dict = {}
    donate = tuple(int(i) + 1 for i in donate_argnums)

    def entry(xs):
        key = _signature(xs)
        e = progs.get(key)
        if e is None:
            apply, consts = _hoist(fn, xs)
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
    run: Callable | None, *, bucket: int, guard: bool = False
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

    return hoisted_jit(fn)
