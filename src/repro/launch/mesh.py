"""Production mesh factories.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — the dry-run must set
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` *before* first jax
initialization, and smoke tests/benches must keep seeing 1 device.
"""
from __future__ import annotations

import jax

from repro.core import compat

__all__ = ["make_production_mesh", "make_mesh", "make_spmm_mesh", "batch_axes"]


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single pod (256 chips) or 2x16x16 (2 pods, 512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return compat.make_mesh(shape, axes)


def make_mesh(pods: int = 1, data: int = 16, model: int = 16):
    """Elastic variant: any (pods, data, model) factorization (launch CLI)."""
    if pods > 1:
        return compat.make_mesh((pods, data, model), ("pod", "data", "model"))
    return compat.make_mesh((data, model), ("data", "model"))


def make_spmm_mesh(n_shards: int, *, axis: str = "shard"):
    """1-D mesh over the first ``n_shards`` devices for the sparse engine.

    Unlike the LM meshes above this deliberately takes a *prefix* of the
    device list, so shard-count sweeps (benchmarks/fig13) can compare
    P in {1, 2, 4, 8} inside one process without re-initializing jax.
    """
    devices = jax.devices()
    if n_shards > len(devices):
        raise ValueError(
            f"asked for {n_shards} shards but only {len(devices)} devices are "
            f"visible (set XLA_FLAGS=--xla_force_host_platform_device_count=N "
            f"before jax initializes to fake more on CPU)"
        )
    return compat.make_mesh((n_shards,), (axis,), devices=devices[:n_shards])


def batch_axes(mesh) -> tuple[str, ...]:
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)
