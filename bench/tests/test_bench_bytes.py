"""Floor bytes on a hand-counted matrix, and the roofline readers on them."""
import pytest

from bench.lib import registry
from bench.lib.floor_bytes import cg_iteration_floor_bytes, spmm_floor_bytes
from bench.lib.harness import RunRecord
from bench.lib.peaks import peak
from bench.lib.trace import TraceSummary

# A 3 x 4 matrix with 5 nonzeros:
#   [[1 0 2 0]
#    [0 0 0 3]
#    [4 0 0 5]]
# float32 values 5 * 4 = 20 bytes; x has 4 rows and y 3, k columns each.


def test_spmm_floor_counts_values_and_vectors_only():
    assert spmm_floor_bytes(5, 3, 4, 1) == 20 + (3 + 4) * 4
    assert spmm_floor_bytes(5, 3, 4, 16) == 20 + (3 + 4) * 16 * 4


def test_cg_iteration_floor():
    # 3 x 3 SPD operator with 7 nonzeros; x, r, p read and written once.
    assert cg_iteration_floor_bytes(7, 3) == 7 * 4 + 6 * 3 * 4


def test_unknown_device_kind_is_an_error():
    assert peak("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peak("cpu")


def _trace(module_seconds):
    return TraceSummary(window_s=2.0, busy_s=1.5, n_devices=1,
                        modules={"jit_apply": [3, module_seconds],
                                 "jit__getitem": [9, 0.25]},
                        ops={}, gaps=[], gap_totals={})


def test_serve_roofline_reader():
    run = RunRecord(3, 4, 5, "TPU v5 lite",
                    {"engine": {"padded_cols": 6, "occupied_cols": 18,
                                "dispatched": {1: 2, 16: 1}}},
                    _trace(1e-6))
    floor = 2 * spmm_floor_bytes(5, 3, 4, 1) + spmm_floor_bytes(5, 3, 4, 16)
    got = registry.load_reader("kernel_hbm_roofline.serve")(run)
    assert got == pytest.approx(100 * floor / 819e9 / 1e-6)
    assert registry.load_reader("engine.padded_share")(run) == pytest.approx(25.0)
    assert registry.load_reader("device_idle.serve")(run) == pytest.approx(25.0)
    # The open-loop cells' readers are the same reductions under other names.
    assert registry.load_reader("kernel_hbm_roofline.rps")(run) == got
    assert registry.load_reader("device_idle.rps")(run) == pytest.approx(25.0)
    assert registry.load_reader("kernel_hbm_roofline.cg")(run) is None
    assert registry.load_reader("cg.iterations")(run) is None


def test_cg_roofline_reader():
    run = RunRecord(3, 3, 7, "TPU v5 lite",
                    {"cg": {"iterations": [3, 3, 4]}}, _trace(2e-6))
    floor = 10 * cg_iteration_floor_bytes(7, 3)
    got = registry.load_reader("kernel_hbm_roofline.cg")(run)
    assert got == pytest.approx(100 * floor / 819e9 / 2e-6)
    assert registry.load_reader("cg.iterations")(run) == pytest.approx(10 / 3)
    assert registry.load_reader("device_idle.cg")(run) == pytest.approx(25.0)
    assert registry.load_reader("engine.padded_share")(run) is None
    assert registry.load_reader("kernel_hbm_roofline.serve")(run) is None


def test_readers_find_nothing_without_a_trace():
    run = RunRecord(3, 4, 5, "TPU v5 lite",
                    {"engine": {"padded_cols": 0, "occupied_cols": 0,
                                "dispatched": {}}}, None)
    for name in ("kernel_hbm_roofline.serve", "device_idle.serve",
                 "engine.padded_share", "device_idle.rps"):
        assert registry.load_reader(name)(run) is None
