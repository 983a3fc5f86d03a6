"""Double-buffered slab pipelines and lane-dense row access: the machinery
shared by the Pallas kernels.

The paper's headline finding is that SpMV on the Phi is bound by *memory
latency*, not bandwidth — its wins come from software prefetching and enough
threads in flight to hide the ~hundreds-of-cycles HBM round trip (§4.3, and
Fang et al.'s empirical study confirms pipelining/prefetch as the decisive
lever).  The TPU analogue is explicit DMA overlap: while the core works on
slab ``i``, the DMA engines are already filling the other buffer with slab
``i+1``.

:func:`slab_pipeline` is that pattern packaged for use *inside* a Pallas
kernel.  Each operand stream is declared as ``(ref, slab_rows)`` or
``(ref, slab_rows, memory_space)`` — the ref lives in ``pl.ANY`` (HBM for
large arrays) and is consumed ``slab_rows`` leading-dim rows at a time into
VMEM (the default) or SMEM (index streams the scalar core reads).  The
helper allocates a two-slot scratch plus a DMA semaphore pair per stream
and runs the canonical warm-up / start-next / wait-current / compute loop.

Two execution paths, one numerics definition:

* ``pipelined=True`` — manual ``pltpu.make_async_copy`` double buffering
  (works under interpret mode too; CI exercises it for equivalence).
* ``pipelined=False`` — the interpret-mode fallback: the same slab loop over
  views of the operands themselves, no scratch, no semaphores.  This is the
  default under ``interpret=True``.

The compute callback receives *refs* to the current slab of every stream
in both paths, so it can index them with scalars (SMEM streams) or dynamic
slices (VMEM streams) — what Mosaic lowers, where dynamic indexing of a
loaded array value does not.

Lane-dense row access (:func:`lane_dense`, :func:`add_to_elem`).  Mosaic
has no vector gather or scatter across vregs (``xv[idx]`` on a 1-D VMEM
array is refused with "Only 2D gather is supported", ``.at[idx].add``
with "Unimplemented primitive ... scatter-add").  What it does lower is a
dynamic sublane slice ``ref[pl.ds(r, 1), :]``.  So a vector the kernels
index element-wise is kept as a lane-dense ``(rows, 128)`` array: element
``i`` sits at row ``i >> 7``, lane ``i & 127``; a scalar index from SMEM
picks the row, and a lane mask picks the element.
"""
from __future__ import annotations

from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = [
    "slab_pipeline",
    "resolve_pipelined",
    "lane_dense",
    "lane_iota",
    "add_to_elem",
    "LANES",
    "SMEM",
    "VMEM_BUDGET_BYTES",
    "VMEM_LIMIT_BYTES",
]

N_BUFFERS = 2  # double buffering: one slab in compute, one in flight
LANES = 128
SMEM = pltpu.SMEM

# VMEM sizing.  A v5e TensorCore has 128 MiB of VMEM, but the compiler's
# default scoped limit is smaller, so every kernel passes
# ``vmem_limit_bytes=VMEM_LIMIT_BYTES`` explicitly.  The tuner enumerates a
# kernel only when its resident operands (x, an x window, an output
# accumulator) fit VMEM_BUDGET_BYTES; the limit leaves the other half for
# the pipeline's double-buffered slabs and the compiler's own scratch.
VMEM_BUDGET_BYTES = 32 * 1024 * 1024
VMEM_LIMIT_BYTES = 2 * VMEM_BUDGET_BYTES


def resolve_pipelined(pipelined: bool | None, interpret: bool) -> bool:
    """Default policy: DMA pipeline when compiled, direct loads in interpret.

    Callers may force ``pipelined=True`` under interpret (the jax TPU
    interpreter models DMA semaphores) — the equivalence tests do exactly
    that — but the safe default keeps interpret runs on the plain-load path.
    """
    return (not interpret) if pipelined is None else bool(pipelined)


def lane_dense(v: jax.Array, rows_multiple: int = 8) -> jax.Array:
    """(n,) -> zero-padded (rows, 128), rows a multiple of ``rows_multiple``."""
    n = v.shape[0]
    per = LANES * rows_multiple
    pad = -(-max(n, 1) // per) * per
    return jnp.zeros((pad,), v.dtype).at[:n].set(v).reshape(pad // LANES, LANES)


def lane_iota() -> jax.Array:
    """(1, 128) lane indices, the mask source for element access."""
    return jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)


def add_to_elem(ref, i, value, lane) -> None:
    """``flat(ref)[i] += value`` on a lane-dense VMEM ref (scalar ``i``).

    ``value`` may be a scalar or a (1, 1) vector; one dynamic row load, a
    masked add and one row store.
    """
    r = i >> 7
    row = ref[pl.ds(r, 1), :]
    ref[pl.ds(r, 1), :] = row + jnp.where(lane == (i & (LANES - 1)), value, 0)


def slab_pipeline(
    body: Callable[..., None],
    streams: Sequence[tuple],
    stop,
    *,
    start=0,
    pipelined: bool = True,
) -> None:
    """Run ``body(s, *slab_refs)`` for ``s`` in ``[start, stop)`` with slab
    ``s`` of every stream resident, double-buffering the copies.

    streams: ``(ref, slab_rows)`` or ``(ref, slab_rows, memory_space)``
    (``SMEM`` for index streams, VMEM otherwise); slab ``s`` of a stream is
    ``ref[s*slab_rows : (s+1)*slab_rows, ...]`` (leading-dim slicing, so a
    per-slab-stacked operand uses ``slab_rows=1`` and indexes axis 0).
    Every slab must lie inside its ref — pad at prepare time, never in the
    kernel.  ``start``/``stop`` may be traced scalars (a grid step's own
    range of slabs).

    ``body`` must only *accumulate* into output refs (or write disjoint
    slices per ``s``): it runs inside a sequential ``fori_loop``.
    """
    streams = [
        (s[0], int(s[1]), s[2] if len(s) > 2 else None) for s in streams
    ]

    if not pipelined:
        def plain_step(s, _):
            body(s, *[ref.at[pl.ds(s * rows, rows)] for ref, rows, _ in streams])
            return 0

        jax.lax.fori_loop(start, stop, plain_step, 0)
        return

    def buffer(scratch, rows, space, slot):
        # SMEM streams are 1-D and double-buffered end to end in one flat
        # scratch: Mosaic tiles a 2-D SMEM array's leading dim in pairs, so
        # a (2, rows) scratch cannot be sliced one slot at a time.
        if space is SMEM:
            return scratch.at[pl.ds(slot * rows, rows)]
        return scratch.at[slot]

    def scoped(*alloc):
        scratches = alloc[: len(streams)]
        sems = alloc[len(streams):]

        def bufs(slot):
            return [
                buffer(scratch, rows, space, slot)
                for (_, rows, space), scratch in zip(streams, scratches)
            ]

        def dmas(s, slot):
            return [
                pltpu.make_async_copy(
                    ref.at[pl.ds(s * rows, rows)], buf, sem.at[slot]
                )
                for (ref, rows, _), buf, sem in zip(streams, bufs(slot), sems)
            ]

        @pl.when(start < stop)
        def _warmup():  # first slab into buffer 0
            for d in dmas(start, 0):
                d.start()

        def step(s, _):
            slot = (s - start) % N_BUFFERS

            @pl.when(s + 1 < stop)
            def _prefetch():  # next slab into the other buffer, overlapped
                for d in dmas(s + 1, 1 - slot):
                    d.start()

            for d in dmas(s, slot):
                d.wait()
            body(s, *bufs(slot))
            return 0

        jax.lax.fori_loop(start, stop, step, 0)

    def alloc(ref, rows, space):
        if space is SMEM:
            assert ref.ndim == 1, "SMEM streams are 1-D"
            return SMEM((N_BUFFERS * rows,), ref.dtype)
        return pltpu.VMEM((N_BUFFERS, rows) + ref.shape[1:], ref.dtype)

    pl.run_scoped(
        scoped,
        *[alloc(ref, rows, space) for ref, rows, space in streams],
        *[pltpu.SemaphoreType.DMA((N_BUFFERS,)) for _ in streams],
    )
