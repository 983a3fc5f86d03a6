"""jax API shims, centralized for the installed jax (0.9).

* ``CompilerParams`` / ``shard_map`` — the names every kernel and mesh
  program imports, so the next rename is a one-file fix.
* :func:`make_mesh` — the one place meshes are built.  ``jax.make_mesh``
  defaults to *Explicit* axes on jax 0.9, under which a plain ``jax.jit``
  over sharded operands refuses to run outside a ``jax.set_mesh`` context;
  every mesh program here is written for *Auto* axes (shard_map plus
  sharding propagation), so the axes are requested as such.
"""
from __future__ import annotations

from typing import Sequence

import jax
import numpy as np
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import AxisType

__all__ = ["CompilerParams", "shard_map", "make_mesh"]

CompilerParams = pltpu.CompilerParams
shard_map = jax.shard_map


def make_mesh(
    shape: Sequence[int], axes: Sequence[str], *, devices=None
) -> jax.sharding.Mesh:
    """A mesh of ``shape`` over ``axes`` with Auto axis types.

    ``devices=None`` lets jax lay all of its devices out for the physical
    topology; an explicit list (e.g. a prefix of ``jax.devices()``) is used
    in the order given.
    """
    shape = tuple(int(s) for s in shape)
    axis_types = (AxisType.Auto,) * len(axes)
    if devices is None:
        return jax.make_mesh(shape, tuple(axes), axis_types=axis_types)
    return jax.sharding.Mesh(
        np.asarray(devices).reshape(shape), tuple(axes), axis_types=axis_types
    )
