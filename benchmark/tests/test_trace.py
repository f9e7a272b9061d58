import gzip
import os
from types import SimpleNamespace

import pytest

from benchmark import trace, xspace

DATA = os.path.join(os.path.dirname(__file__), "data", "agg32.xplane.pb.gz")


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    """One traced solve of poisson7_agg at 32^3 on a TPU v5 lite,
    recorded through the harness (benchmark/tests/record_trace.py)."""
    path = tmp_path_factory.mktemp("trace") / "agg32.xplane.pb"
    path.write_bytes(gzip.open(DATA).read())
    return trace.load(str(path))


def test_recorded_window_and_busy(recorded):
    (chip,) = recorded.chips
    assert chip.name == "/device:TPU:0"
    assert 0 < chip.busy_ns <= recorded.window_ns
    # self times partition the busy time: nesting is resolved
    assert sum(op.self_ns for op in chip.ops) == pytest.approx(chip.busy_ns,
                                                               rel=1e-9)
    assert recorded.busy_s == chip.busy_ns / 1e9


def test_recorded_scopes_and_kernels(recorded):
    ops = recorded.chips[0].ops
    dia = [op for op in ops if op.short_name == "_pallas_dia_spmv"]
    assert dia and all(op.leaf and op.category == "custom-call" for op in dia)
    scopes = {op.scope for op in dia}
    assert "jit(solve)/while/body/amg_l0_presmooth/jit(_pallas_dia_spmv)/" \
           "pallas_call:" in scopes
    assert any("amg_coarse_solve" in op.scope for op in ops)
    whiles = [op for op in ops if op.category == "while"]
    assert whiles and not any(op.leaf for op in whiles)
    assert not any(op.collective for op in ops)


def test_recorded_breakdown(recorded):
    b = recorded.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert sum(s for _, s in b["idle_gaps"]) == pytest.approx(
        (recorded.window_ns - recorded.chips[0].busy_ns) / 1e9, rel=1e-9)
    assert any(name.startswith("bench_block") for name, _ in b["idle_gaps"])


def test_xspace_metadata_of_recorded_trace():
    md = xspace.op_metadata(gzip.open(DATA).read())
    assert set(md) >= {"/device:TPU:0"}
    table = md["/device:TPU:0"]
    kernels = [v for k, v in table.items() if k.startswith("%_pallas_dia")]
    assert kernels and all(v["hlo_category"] == "custom-call"
                           for v in kernels)
    assert all("pallas_call" in v["tf_op"] for v in kernels)


def _ev(name, start, dur):
    return SimpleNamespace(name=name, start_ns=start, duration_ns=dur)


def test_nesting_self_time_and_window_clip():
    line = SimpleNamespace(events=[
        _ev("%while.1", 100, 100),
        _ev("%fusion.2", 110, 30),
        _ev("%all-reduce.3", 150, 20),
        _ev("%fusion.4", 300, 50),
        _ev("%fusion.5", 900, 10),  # after the window
    ])
    meta = {"%all-reduce.3": {"hlo_category": "all-reduce", "tf_op": "a/b"}}
    chip = trace._chip("/device:TPU:0", line, meta, (0, 400))
    assert [op.self_ns for op in chip.ops] == [50, 30, 20, 50]
    assert [op.leaf for op in chip.ops] == [False, True, True, True]
    assert [op.collective for op in chip.ops] == [False, False, True, False]
    assert chip.ops[2].scope == "a/b"
    assert chip.busy == [(100, 200), (300, 350)]
    t = trace.Trace((0, 400), [chip], [("bench_block", 0, 400),
                                       ("bench_window", 0, 400)])
    gaps = t.breakdown()["idle_gaps"]
    assert gaps == [["bench_block (3 gaps)", 250 / 1e9]]


def test_short_name():
    op = trace.Op("%_pallas_dia_spmv.43 = f32[1,256,128] custom-call()",
                  0, 1, 1, "", "custom-call")
    assert op.short_name == "_pallas_dia_spmv"


def test_recorded_kernels_read_their_hbm_bytes(recorded):
    """At 32^3 XLA brings every operand of the DIA kernel on chip first."""
    dia = [op for op in recorded.chips[0].ops
           if op.short_name == "_pallas_dia_spmv"]
    assert dia and all(op.hbm_bytes == 0 for op in dia)
    starts = [op for op in recorded.chips[0].ops
              if op.short_name == "copy-start"]
    assert starts and all(op.hbm_bytes > 0 for op in starts)


@pytest.mark.parametrize("text,moved", [
    ("%k.1 = f32[1,256,128]{2,1,0:T(8,128)} custom-call(f32[280,128]"
     "{1,0:T(8,128)S(1)} %x, bf16[7,256,128]{2,1,0:T(8,128)} %d), "
     "operand_layout_constraints={f32[280,128]{1,0}}",
     256 * 128 * 4 + 7 * 256 * 128 * 2),
    ("%f.2 = (f32[8]{0}, s32[4]{0:S(1)}) fusion(f32[8]{0} %a), kind=kLoop",
     8 * 4 + 8 * 4),
    ("%c.3 = pred[] compare(pred[] %p, pred[] %q), direction=EQ", 3),
    ("%while.4", None),
])
def test_hbm_bytes(text, moved):
    assert trace.Op(text, 0, 1, 1, "", "").hbm_bytes == moved
