"""The unstructured-SpMV readers (``unstructured_spmv_roofline``,
``unstructured_spmv_ms``): their arithmetic on a hand-made record, hand
sums over a recorded classical trace, and nothing to read in the
aggregation trace, whose hierarchy takes no gather."""

import gzip
import importlib
import os

import pytest

from benchmark import trace
from benchmark.run import Record

DATA = os.path.join(os.path.dirname(__file__), "data")
SCOPES = {"spmv_ell", "spmv_well", "spmv_csr"}


def _read(name, record):
    return importlib.import_module(f"benchmark.metrics.{name}").read(record)


def _load(tmp_path_factory, name):
    path = tmp_path_factory.mktemp("trace") / name.removesuffix(".gz")
    path.write_bytes(gzip.open(os.path.join(DATA, name)).read())
    return trace.load(str(path))


def _record(t, iterations=10):
    solves = [{"seconds": 1.0, "iterations": iterations, "converged": True}]
    return Record({}, solves, {}, "TPU v5 lite", t)


@pytest.fixture(scope="module")
def classical(tmp_path_factory):
    """One traced solve of poisson7_classical at 24^3 on a TPU v5 lite,
    recorded through the harness (benchmark/tests/
    record_classical_trace.py).  Most of its ops find their operands
    on chip at this size; a few kernel calls move HBM bytes."""
    return _load(tmp_path_factory, "classical24.xplane.pb.gz")


@pytest.fixture(scope="module")
def aggregation(tmp_path_factory):
    return _load(tmp_path_factory, "agg32.xplane.pb.gz")


# a gather SpMV with every array in HBM: a (4, 1024) s32 and f32 pair,
# x of 2048 words and y of 1024
GATHER = ("%fusion.3 = f32[1024]{0} fusion(s32[4,1024]{1,0} %c, "
          "f32[4,1024]{1,0} %v, f32[2048]{0} %x), kind=kLoop")
GATHER_BYTES = (2 * 4 * 1024 + 2048 + 1024) * 4


def _op(scope, start, end, name=GATHER):
    return trace.Op(name, start, end, end - start, scope, "loop fusion")


def test_arithmetic_on_a_hand_made_record():
    ops = [
        _op("jit(solve)/while/body/amg_l1_presmooth/spmv_ell/mul", 0, 100),
        _op("jit(solve)/while/body/amg_l0_restrict/spmv_csr/reduce", 100,
            300),
        _op("jit(solve)/while/body/spmv_well/jit(_pallas_well_spmv)/pad",
            300, 350, "%copy.1 = f32[2048]{0:S(1)} copy(f32[2048]{0:S(1)} "
            "%x)"),
        # not an unstructured SpMV: no such scope, or only a prefix of one
        _op("jit(solve)/while/body/amg_l1_presmooth/mul", 350, 500),
        _op("jit(solve)/while/body/spmv_ellpack/mul", 500, 600),
    ]
    t = trace.Trace((0, 1000), [trace.Chip("/device:TPU:0", ops,
                                           [(0, 600)])])
    record = _record(t, iterations=5)
    # the on-chip copy moves no HBM bytes: left out of the share only
    assert _read("unstructured_spmv_roofline", record) == pytest.approx(
        100 * 2 * GATHER_BYTES / 819e9 / 300e-9)
    assert _read("unstructured_spmv_ms", record) == pytest.approx(
        350e-6 / 5)


def test_recorded_classical_trace_holds_the_scopes(classical):
    ops = [op for op in classical.chips[0].ops
           if SCOPES & set(op.scope.split("/"))]
    assert ops
    found = {s for op in ops for s in SCOPES & set(op.scope.split("/"))}
    # at 24^3 the chip stores level 1 and the fine R and P as windowed
    # ELL, level 1's P as CSR (at 128^3 levels 1 and 2 are ELL)
    assert found == {"spmv_well", "spmv_csr"}
    assert any(op.short_name == "_pallas_well_spmv"
               and op.category == "custom-call" for op in ops)
    assert any("/amg_l0_restrict/" in op.scope for op in ops)
    assert any("/amg_l1_" in op.scope for op in ops)


def test_readers_equal_hand_sums_on_the_recorded_trace(classical):
    scoped = [op for op in classical.chips[0].ops
              if SCOPES & set(op.scope.split("/"))]
    iterations = 9
    self_ns = 0.0
    moved, took_ns = 0, 0.0
    for op in scoped:
        self_ns += op.self_ns
        if op.hbm_bytes:
            moved += op.hbm_bytes
            took_ns += op.self_ns
    record = _record(classical, iterations)
    assert _read("unstructured_spmv_ms", record) == pytest.approx(
        self_ns / 1e6 / iterations, rel=1e-12)
    share = _read("unstructured_spmv_roofline", record)
    assert share == pytest.approx(100 * moved / 819e9 / (took_ns / 1e9),
                                  rel=1e-12)
    assert 0 < share <= 100


def test_nothing_to_read_in_the_aggregation_trace(aggregation):
    record = _record(aggregation)
    assert _read("unstructured_spmv_roofline", record) is None
    assert _read("unstructured_spmv_ms", record) is None


def test_untraced_reads_nothing():
    record = _record(None)
    assert _read("unstructured_spmv_roofline", record) is None
    assert _read("unstructured_spmv_ms", record) is None
