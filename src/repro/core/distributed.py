"""Distributed SpMV/SpMM: the paper's 61-private-caches problem at mesh scale.

The paper found that the same x entries are re-fetched into many private L2s
(actual traffic up to 1.7x application traffic).  Across chips the same
phenomenon is the collective traffic needed to make x visible to every shard.
Two schedules are provided, both as shard_map programs over a 1-D mesh axis:

* ``allgather_spmm`` — gather all of x to every shard, then local SpMM.
  Simple; collective bytes = (P-1)/P * |x| per shard, all up-front.

* ``ring_spmm`` — A is partitioned (rows x col-slabs); each shard starts with
  its local x-slab and rotates slabs around the ring with
  ``lax.ppermute`` while multiplying the matching column-slab of A.
  Compute and communication overlap step-by-step (the distributed-memory
  answer to the paper's "input vector distribution" future-work note, and the
  same schedule as weight-stationary ring matmuls in TPU LM serving).

Both operate on *stacked* shard arrays built by core.partition, so they jit
under shard_map with static shapes.
"""
from __future__ import annotations

import functools
import warnings
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from .compat import shard_map as _shard_map
from .spmv import _rows_from_indptr

__all__ = ["allgather_spmm", "ring_spmm", "local_spmm", "stacked_spmm",
           "assemble_rows", "SCHEDULES", "build_mesh_operand",
           "place_mesh_operand", "mesh_spmm_runner", "psum_dot_runner"]

SCHEDULES = ("allgather", "ring")


def local_spmm(shard: dict[str, Any], x: jax.Array, n_rows: int) -> jax.Array:
    """Local CSR SpMM on one shard's (padded) arrays. X: (n_local, k).

    Prepared shard dicts (``partition.stack_csr_shards``/``stack_grid_shards``)
    carry the hoisted per-nnz ``rows`` map; raw dicts fall back to deriving
    it per dispatch (compat shim only — the hot paths never take it).
    """
    if "rows" in shard:
        rows = shard["rows"]
    else:
        rows = _rows_from_indptr(
            shard["indptr"], shard["indices"].shape[0], n_rows
        )
    prod = shard["data"][:, None] * x[shard["indices"], :]
    return jax.ops.segment_sum(prod, rows, num_segments=n_rows)


@jax.jit
def stacked_spmm(stacked: dict[str, Any], x: jax.Array) -> jax.Array:
    """Y_p = A_p @ X for every row shard, in ONE batched dispatch.

    The stacked-RHS serving entry point: ``stacked`` is the padded per-shard
    CSR pytree from :func:`core.partition.stack_csr_shards` (leading shard
    dim P), ``x`` the full stacked RHS (n, k).  A single vmap over the shard
    dim replaces P sequential kernel launches, so a batch-aggregating engine
    can run row-partitioned shards under the same dispatch discipline as its
    k-bucketed SpMM plans.  Returns (P, max_rows, k) padded row slabs; use
    :func:`assemble_rows` to stitch the original row order back together.
    """
    n_rows = stacked["indptr"].shape[-1] - 1
    shards = {
        key: stacked[key]
        for key in ("indptr", "indices", "data", "rows")
        if key in stacked
    }
    return jax.vmap(lambda sh: local_spmm(sh, x, n_rows))(shards)


def assemble_rows(ys: jax.Array, n_rows: Any) -> jax.Array:
    """Concatenate (P, max_rows, k) padded shard outputs to (sum rows, k).

    ``n_rows`` is the per-shard valid row count (host array, e.g. the
    ``n_rows`` entry of ``stack_csr_shards`` or ``diff(RowPartition.bounds)``).
    """
    counts = [int(r) for r in np.asarray(n_rows)]
    return jnp.concatenate([ys[p, :r] for p, r in enumerate(counts)], axis=0)


def allgather_spmm(mesh, axis: str, stacked: dict[str, Any], x_sharded: jax.Array):
    """Y = A @ X with A row-partitioned and X all-gathered per shard.

    stacked: per-shard padded CSR arrays with a leading shard dim (see
    core.partition.stack_csr_shards), already placed with that dim over
    ``axis``.  x_sharded: (P * n_local, k) row-sharded over ``axis``.
    """
    n_rows = stacked["indptr"].shape[-1] - 1

    @functools.partial(
        _shard_map,
        mesh=mesh,
        in_specs=(P(axis), P(axis)),
        out_specs=P(axis),
    )
    def run(shard, x_local):
        shard = jax.tree.map(lambda a: a[0], shard)  # drop unit shard dim
        x_full = jax.lax.all_gather(x_local, axis, tiled=True)
        return local_spmm(shard, x_full, n_rows)[None]

    return run(stacked, x_sharded)


def ring_spmm(mesh, axis: str, stacked_grid: dict[str, Any], x_sharded: jax.Array):
    """Ring-rotated SpMM: A (rows x col-slab) shards, x-slabs ppermute rotation.

    stacked_grid: padded CSR arrays with leading dims (P_row_shard, P_col_slab)
    where the row-shard dim is over ``axis`` and the col-slab dim is local;
    shard p holds its row-slab of A split into P column slabs with slab-local
    column indices.  Step s multiplies slab ((p + s) mod P) against the
    x-slab currently held, then rotates x to the next shard.  P-1 rotations;
    each overlaps with one local SpMM.
    """
    n_rows = stacked_grid["indptr"].shape[-1] - 1
    n_steps = jax.device_count() if mesh is None else mesh.shape[axis]

    @functools.partial(
        _shard_map,
        mesh=mesh,
        in_specs=(P(axis), P(axis)),
        out_specs=P(axis),
    )
    def run(grid_shard, x_local):
        grid_shard = jax.tree.map(lambda a: a[0], grid_shard)  # (P_col, ...)
        x_local = x_local  # (n_local, k)
        p = jax.lax.axis_index(axis)

        def step(carry, s):
            x_slab, acc = carry
            slab_id = (p + s) % n_steps
            sub = jax.tree.map(lambda a: a[slab_id], grid_shard)
            acc = acc + local_spmm(sub, x_slab, n_rows)
            # Rotate x backwards around the ring so shard p sees slab p+s+1.
            nxt = jax.lax.ppermute(
                x_slab,
                axis,
                perm=[(i, (i - 1) % n_steps) for i in range(n_steps)],
            )
            return (nxt, acc), None

        acc0 = jnp.zeros((n_rows, x_local.shape[-1]), x_local.dtype)
        # The accumulator must be marked device-varying for the scan carry
        # (newer jax requires an explicit pcast; older versions have no such
        # notion and the zeros carry is already fine).
        if hasattr(jax.lax, "pcast"):
            acc0 = jax.lax.pcast(acc0, (axis,), to="varying")
        init = (x_local, acc0)
        (x_final, acc), _ = jax.lax.scan(
            step, init, jnp.arange(n_steps, dtype=jnp.int32)
        )
        del x_final
        return acc[None]

    return run(stacked_grid, x_sharded)


# ---------------------------------------------------------------------------
# Mesh operands: host-side partition + stack for one collective schedule
# ---------------------------------------------------------------------------
def build_mesh_operand(a, n_shards: int, schedule: str) -> dict[str, Any]:
    """Partition ``a`` for one collective schedule; host arrays only.

    * ``allgather`` — nnz-balanced row shards (``partition.rows_balanced``),
      each holding global column indices; x is gathered whole per shard.
    * ``ring`` — an (P x P) row-slab x col-slab grid
      (``partition.grid_2d`` + ``stack_grid_shards``): shard p starts with
      x-slab p and rotates slabs with ``ppermute``, multiplying the matching
      column slab each step.  Columns are zero-padded to a multiple of P so
      the x-slabs divide the mesh axis evenly (the padded tail of x is zero
      and no stored entry references it).

    Returns the stacked arrays plus assembly metadata (``shard_rows``,
    ``n_pad``); :func:`place_mesh_operand` moves the arrays onto a mesh.
    """
    from .formats import CSRMatrix
    from .partition import grid_2d, rows_balanced, stack_csr_shards, \
        stack_grid_shards

    P_ = int(n_shards)
    m, n = a.shape
    n_pad = -(-n // P_) * P_
    if schedule == "allgather":
        part = rows_balanced(a, P_)
        stacked = stack_csr_shards(part.shards)
        shard_rows = np.diff(part.bounds)
    elif schedule == "ring":
        a_pad = a if n_pad == n else CSRMatrix(
            (m, n_pad), a.indptr, a.indices, a.data
        )
        stacked = stack_grid_shards(grid_2d(a_pad, (P_, P_)))
        shard_rows = stacked["n_rows"].astype(np.int64)
    else:
        raise ValueError(f"unknown schedule {schedule!r}; use one of {SCHEDULES}")
    arrays = {
        key: stacked[key]
        for key in ("indptr", "indices", "data", "rows")
        if key in stacked
    }
    return {
        "schedule": schedule,
        "n_shards": P_,
        "arrays": arrays,
        "shard_rows": shard_rows,
        "n_pad": n_pad,
        "shape": (m, n),
    }


def place_mesh_operand(prep: dict[str, Any], mesh, axis: str) -> dict[str, Any]:
    """Move a :func:`build_mesh_operand` result's arrays onto the mesh.

    The leading (row-shard) dim goes over ``axis``; the ring grid's col-slab
    dim stays local to each shard.
    """
    sharding = jax.sharding.NamedSharding(mesh, P(axis))
    placed = {
        key: jax.device_put(jnp.asarray(v), sharding)
        for key, v in prep["arrays"].items()
    }
    return {**prep, "placed": placed}


def psum_dot_runner(mesh, axis: str, n: int):
    """Bind ``dot(u, v) -> scalar`` as a shard_map + ``lax.psum`` program.

    The fused solver runtime's mesh path needs its dot-product reductions
    (rᵀr, pᵀAp, Rayleigh quotients) to run as collectives on the SAME mesh
    axis the tuned SpMV schedule shards over — a host-side ``jnp.vdot`` on
    a sharded vector would leave the reduction layout to late GSPMD
    propagation instead of the mesh schedule the plan was measured on.
    Vectors are zero-padded to a multiple of the shard count (pad
    contributes 0 to the sum), each shard reduces its slab locally, and one
    ``psum`` over ``axis`` replicates the scalar.

    ``u``/``v`` may be (n,) or (n, k); (n, k) reduces per column -> (k,)
    (the block solvers' per-vector Rayleigh quotients in one collective).
    """
    P_ = int(mesh.shape[axis])
    n_pad = -(-int(n) // P_) * P_

    @functools.partial(
        _shard_map, mesh=mesh, in_specs=(P(axis), P(axis)), out_specs=P()
    )
    def reduce_(ul, vl):
        return jax.lax.psum(jnp.sum(ul * vl, axis=0), axis)

    @jax.jit
    def dot(u, v):
        u2 = u[:, None] if u.ndim == 1 else u
        v2 = v[:, None] if v.ndim == 1 else v
        if n_pad > u2.shape[0]:
            pad = jnp.zeros((n_pad - u2.shape[0], u2.shape[1]), u2.dtype)
            u2 = jnp.concatenate([u2, pad], axis=0)
            v2 = jnp.concatenate(
                [v2, jnp.zeros((n_pad - v2.shape[0], v2.shape[1]), v2.dtype)],
                axis=0,
            )
        out = reduce_(u2, v2)
        return out[0] if u.ndim == 1 else out

    return dot


def mesh_spmm_runner(mesh, axis: str, prep: dict[str, Any],
                     donate_rhs: bool = False):
    """Bind a placed mesh operand into ``fn(x) -> y`` for serving.

    ``x`` may be (n,) or (n, k); it is zero-padded to the schedule's padded
    column count, row-sharded over ``axis``, pushed through the shard_map
    program, and the padded per-shard row slabs are stitched back into the
    original row order.  Everything past the placement — padding, the
    collective schedule, and the slab stitch (``shard_rows``/``n_pad`` are
    static host constants) — compiles into ONE jitted program.  The placed
    shard arrays enter it as arguments: closed over, they would be written
    into the program as (replicated) constants.

    ``donate_rhs=True`` additionally donates the RHS buffer to the program
    (the serving engine owns its assembled batch slabs outright and never
    reads one after dispatch).  Callers that reuse one ``x`` across calls —
    the measured search's ``time_fn`` loop — must keep the default.
    """
    P_ = prep["n_shards"]
    n_pad = prep["n_pad"]
    shard_rows = prep["shard_rows"]
    placed = prep["placed"]
    sched = allgather_spmm if prep["schedule"] == "allgather" else ring_spmm
    x_sharding = jax.sharding.NamedSharding(mesh, P(axis))

    @functools.partial(jax.jit, donate_argnums=(1,) if donate_rhs else ())
    def run(arrays, x2):
        if x2.shape[0] < n_pad:
            pad = jnp.zeros((n_pad - x2.shape[0], x2.shape[1]), x2.dtype)
            x2 = jnp.concatenate([x2, pad], axis=0)
        ys = sched(mesh, axis, arrays, x2).reshape(P_, -1, x2.shape[1])
        return assemble_rows(ys, shard_rows)

    # The "donated buffers were not usable" diagnostic can only fire while
    # a new shape compiles; donation is best-effort by contract here (when
    # no output aliases the RHS, XLA ignores it), so suppress it for
    # exactly those compiles — scoped per call-shape, never process-global,
    # and with zero steady-state cost once a shape is warm.
    warmed_shapes: set = set()

    def call(x2):
        if donate_rhs and x2.shape not in warmed_shapes:
            with warnings.catch_warnings():
                warnings.filterwarnings(
                    "ignore", message="Some donated buffers were not usable"
                )
                y = run(placed, x2)
            warmed_shapes.add(x2.shape)
            return y
        return run(placed, x2)

    def fn(x):
        x2 = x[:, None] if x.ndim == 1 else x
        y = call(jax.device_put(x2, x_sharding) if x2.shape[0] == n_pad
                 else x2)
        return y[:, 0] if x.ndim == 1 else y

    return fn
