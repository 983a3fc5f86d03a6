"""core.compat mesh construction and the compile-cache entry-point helper."""
import jax
import pytest
from jax.sharding import AxisType

from repro.core import compat
from repro.core.compile_cache import (
    CACHE_ENV,
    default_cache_dir,
    enable_compile_cache,
)


def test_make_mesh_uses_auto_axes():
    mesh = compat.make_mesh((1,), ("x",))
    assert mesh.axis_types == (AxisType.Auto,)
    prefix = compat.make_mesh((1,), ("shard",), devices=jax.devices()[:1])
    assert prefix.axis_names == ("shard",)
    assert prefix.axis_types == (AxisType.Auto,)


@pytest.fixture
def cache_config():
    before = jax.config.jax_compilation_cache_dir
    min_secs = jax.config.jax_persistent_cache_min_compile_time_secs
    yield
    jax.config.update("jax_compilation_cache_dir", before)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", min_secs)


def test_compile_cache_defers_to_the_environment(
    cache_config, monkeypatch, tmp_path
):
    monkeypatch.setenv(CACHE_ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # nothing set
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_compile_cache_defaults_to_the_checkout(cache_config, monkeypatch):
    monkeypatch.delenv(CACHE_ENV, raising=False)
    path = enable_compile_cache()
    assert path == str(default_cache_dir())
    assert path.endswith("/.jax_cache")
    assert (default_cache_dir().parent / "src" / "repro").is_dir()
    assert jax.config.jax_compilation_cache_dir == path
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
