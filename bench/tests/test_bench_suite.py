"""The benchmark's matrices are the program's: its generator copy gives the
same pattern as ``repro.data.suite.generate``, and its ``spd_shift`` copy
the same CSR as ``repro.core.spmv.spd_shift``, bit for bit."""
import numpy as np
import pytest

from bench.lib import registry
from bench.lib.suite import Csr, PatternStore, make_pattern, rng_for, spd_shift

from repro.core.formats import CSRMatrix
from repro.core.spmv import spd_shift as program_spd_shift
from repro.data.suite import SUITE, generate

CONFIGS = ["ldoor", "webbase-1M"]


def _table1(spec):
    return {"idx": spec.idx, "n_rows": spec.n_rows, "nnz": spec.nnz,
            "family": spec.family, "band": spec.band, "max_row": spec.max_row}


@pytest.mark.parametrize("name", CONFIGS)
def test_config_pattern_matches_program_generator(name):
    cfg = registry.load_config(name)
    spec = next(s for s in SUITE if s.name == name)
    assert cfg["generator"] == _table1(spec)
    ref = generate(name, scale=0.01, seed=cfg["structure_seed"])
    n, indptr, indices = make_pattern(cfg["generator"], 0.01, cfg["structure_seed"])
    assert (n, n) == ref.shape
    np.testing.assert_array_equal(indptr, ref.indptr)
    np.testing.assert_array_equal(indices, ref.indices)


@pytest.mark.parametrize("name", ["mesh_2048", "cage14", "nd24k", "inline_1"])
def test_every_family_matches_program_generator(name):
    spec = next(s for s in SUITE if s.name == name)
    ref = generate(name, scale=0.002, seed=3)
    _, indptr, indices = make_pattern(_table1(spec), 0.002, 3)
    np.testing.assert_array_equal(indptr, ref.indptr)
    np.testing.assert_array_equal(indices, ref.indices)


@pytest.mark.parametrize("name", CONFIGS)
def test_spd_shift_matches_program(name, tmp_path):
    cfg = registry.load_config(name)
    store = PatternStore(name, cfg["generator"], 0.01, cfg["structure_seed"], tmp_path)
    a = store.matrix(2**40 + 11)
    mine = spd_shift(a, store.spd_pattern(), margin=cfg["cg"]["spd_margin"])
    theirs = program_spd_shift(CSRMatrix(a.shape, a.indptr, a.indices, a.data),
                               margin=cfg["cg"]["spd_margin"])
    for key in ("indptr", "indices", "data"):
        assert getattr(mine, key).dtype == getattr(theirs, key).dtype
        np.testing.assert_array_equal(getattr(mine, key), getattr(theirs, key))


def test_pattern_cache_round_trip_and_values_follow_the_seed(tmp_path):
    cfg = registry.load_config("ldoor")
    first = PatternStore("ldoor", cfg["generator"], 0.005, 0, tmp_path)
    a1 = first.matrix(7)
    first.spd_pattern()
    assert first.hits == {"a": False, "spd": False}
    second = PatternStore("ldoor", cfg["generator"], 0.005, 0, tmp_path)
    a2 = second.matrix(7)
    second.spd_pattern()
    assert second.hits == {"a": True, "spd": True}
    np.testing.assert_array_equal(a1.indices, a2.indices)
    np.testing.assert_array_equal(a1.data, a2.data)
    assert not np.array_equal(a1.data, second.matrix(8).data)
    # Another structure seed is another deployment, and another cache file.
    other = PatternStore("ldoor", cfg["generator"], 0.005, 1, tmp_path)
    other.a_pattern()
    assert other.hits == {"a": False}


def test_seed_streams_take_any_whole_number():
    for seed in (0, 2**31 + 5, 2**64 + 3, -1):
        assert rng_for(seed, 0).integers(1 << 30) >= 0
    assert rng_for(5, 0).integers(1 << 30) != rng_for(5, 1).integers(1 << 30)


@pytest.mark.parametrize("name", CONFIGS)
def test_config_states_what_its_generator_builds(name):
    cfg = registry.load_config(name)
    n, indptr, _ = make_pattern(cfg["generator"], cfg["scale"], cfg["structure_seed"])
    assert (n, int(indptr[-1]), int(np.diff(indptr).max())) == (
        cfg["n_rows"], cfg["nnz"], cfg["max_row"])


@pytest.mark.parametrize("name", CONFIGS)
def test_reduced_names_every_departure_from_the_published_matrix(name):
    cfg = registry.load_config(name)
    published = cfg["published"]
    assert sorted(k for k in published if cfg[k] != published[k]) == sorted(cfg["reduced"])


def test_a_matrix_other_than_the_stated_one_is_refused():
    from bench.lib.harness import check_stated

    cfg = registry.load_config("webbase-1M")
    n, indptr, indices = make_pattern(cfg["generator"], 0.01, cfg["structure_seed"])
    a = Csr((n, n), indptr, indices, np.ones(indices.shape, np.float32))
    with pytest.raises(ValueError, match="states"):
        check_stated(cfg, a)
