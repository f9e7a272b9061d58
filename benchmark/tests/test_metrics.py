"""Each metric's arithmetic on a hand-made record."""

import importlib

import pytest

from benchmark import trace
from benchmark.run import Record


def _read(name, record):
    return importlib.import_module(f"benchmark.metrics.{name}").read(record)


def _op(scope, start, end, name="%fusion.1 = f32[] fusion()",
        category="loop fusion"):
    return trace.Op(name, start, end, end - start, scope, category)


# a fine apply with every array in HBM: (7 + 1) x 1024 words read, 1024
# written; and one whose x (the 1040-word operand) was brought on chip
DIA = ("%_pallas_dia_spmv.7 = f32[1024]{0} custom-call(f32[1040]{0} %x, "
       "f32[7,1024]{1,0} %d), custom_call_target=\"tpu_custom_call\", "
       "operand_layout_constraints={f32[1040]{0}, f32[7,1024]{1,0}}")
DIA_X_ON_CHIP = DIA.replace("f32[1040]{0} %x", "f32[1040]{0:S(1)} %x")


@pytest.fixture
def record():
    ops = [
        _op("jit(solve)/while/body/jit(_pallas_dia_spmv)/pallas_call:", 0,
            400, DIA, "custom-call"),
        _op("jit(solve)/while/body/amg_l0_presmooth/mul:", 400, 500),
        _op("jit(solve)/while/body/amg_l1_presmooth/jit(_pallas_dia_spmv)/"
            "pallas_call:", 500, 550, DIA, "custom-call"),
        _op("jit(solve)/while/body/amg_coarse_solve/triangular_solve:", 550,
            600),
        _op("jit(solve)/while/body/amg_l0_postsmooth/jit(_pallas_dia_spmv)/"
            "pallas_call:", 700, 1100, DIA_X_ON_CHIP, "custom-call"),
        _op("jit(solve)/while/body/dot_general:", 1100, 1200),
    ]
    chip = trace.Chip("/device:TPU:0", ops,
                      [(0, 600), (700, 1200)])
    t = trace.Trace((0, 2000), [chip], [])
    solves = [{"seconds": 2.0, "iterations": 20, "converged": True},
              {"seconds": 4.0, "iterations": 25, "converged": True}]
    setup = {"total_s": 50.0, "program_s": 30.0, "program_compile_s": 5.0,
             "compile_s": 12.0, "generate_s": 2.0, "warm_s": 3.0}
    structure = {"fine": {"format": "DIA", "rows": 1024}}
    return Record(setup, solves, structure, "TPU v5 lite", t)


def test_end_to_end(record):
    assert _read("setup_s", record) == 50.0
    assert _read("solve_s", record) == 3.0


def test_setup_layer(record):
    assert _read("amg_setup_s", record) == 25.0
    assert _read("setup_compile_s", record) == 12.0


def test_pcg_iterations(record):
    assert _read("pcg_iterations", record) == 22.5
    assert _read("pcg_iteration_ms", record) == pytest.approx(
        1e3 * 6.0 / 45)


def test_level_shares(record):
    busy = 1100.0
    assert _read("fine_level_share", record) == pytest.approx(
        100 * 500 / busy)
    assert _read("coarse_levels_share", record) == pytest.approx(
        100 * 100 / busy)


def test_device_idle_share(record):
    assert _read("device_idle_share", record) == pytest.approx(
        100 * (1 - 1100 / 2000))


def test_fine_spmv_roofline(record):
    # two fine-level applies (the Krylov SpMV and amg_l0_postsmooth) in
    # 800 ns at 819 GB/s; the second's x was on chip before it ran
    moved = (1040 + 7 * 1024 + 1024) * 4 + (7 * 1024 + 1024) * 4
    assert _read("fine_spmv_roofline", record) == pytest.approx(
        100 * moved / 819e9 / 800e-9)


def test_roofline_leaves_out_calls_that_move_no_hbm_bytes(record):
    on_chip = DIA.replace("{0}", "{0:S(1)}").replace("{1,0}", "{1,0:S(1)}")
    for op in record.trace.chips[0].ops:
        if op.short_name == "_pallas_dia_spmv" and "postsmooth" in op.scope:
            op.name = on_chip
    moved = (1040 + 7 * 1024 + 1024) * 4
    assert _read("fine_spmv_roofline", record) == pytest.approx(
        100 * moved / 819e9 / 400e-9)
    for op in record.trace.chips[0].ops:
        if op.short_name == "_pallas_dia_spmv":
            op.name = on_chip
    assert _read("fine_spmv_roofline", record) is None


def test_untraced_and_unknown_format_read_nothing(record):
    structure = dict(record.structure, fine={"format": "CSR"})
    other = Record(record.setup, record.solves, structure, "TPU v5 lite",
                   record.trace)
    assert _read("fine_spmv_roofline", other) is None
    bare = Record(record.setup, record.solves, record.structure,
                  "TPU v5 lite")
    for name in ("fine_level_share", "coarse_levels_share",
                 "fine_spmv_roofline", "device_idle_share"):
        assert _read(name, bare) is None


def test_peaks_table():
    assert Record({}, [], {}, "TPU v5 lite").peak["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        Record({}, [], {}, "TPU v9 imaginary").peak
