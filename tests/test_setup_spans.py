"""Set-up spans on the profiler's clock (core/profiling.py): every
``setup_phase`` is a ``setup:<phase>`` TraceAnnotation of one kind (host,
device or transfer); a scope's self-time totals and its compile time
partition its wall time; ``Solver.solve`` opens ``solve`` and
``solve:dispatch`` host spans."""

import glob
import json
import time

import pytest

import jax

from amgx_tpu.amg import aggregation
from amgx_tpu.config.amg_config import AMGConfig
from amgx_tpu.core.matrix import SparseMatrix
from amgx_tpu.core.profiling import (
    SETUP_TOTALS,
    SPAN_KINDS,
    setup_profile_scope,
)
from amgx_tpu.io.poisson import poisson_rhs, poisson_scipy
from amgx_tpu.solvers import create_solver

_AMG = (
    '{"config_version": 2, "solver": {"scope": "main", "solver": "PCG", '
    '"max_iters": 100, "tolerance": 1e-8, "convergence": "RELATIVE_INI", '
    '"monitor_residual": 1, "preconditioner": {"scope": "amg", '
    '"solver": "AMG", %s, "smoother": {"scope": "j", '
    '"solver": "BLOCK_JACOBI", "relaxation_factor": 0.8, '
    '"monitor_residual": 0}, "max_iters": 1, "min_coarse_rows": 32, '
    '"coarse_solver": "DENSE_LU_SOLVER", "monitor_residual": 0}}}'
)
AGGREGATION = _AMG % '"algorithm": "AGGREGATION", "selector": "SIZE_8"'
CLASSICAL = _AMG % (
    '"algorithm": "CLASSICAL", "selector": "PMIS", "interpolator": "D1", '
    '"setup_location": "DEVICE"'
)
# the phase keys the profile had before the spans got kinds
AGGREGATION_PHASES = {"host_csr", "aggregation", "interp", "rap_execute",
                      "transfer", "finalize", "coarse_factor"}
# level 0's device Galerkin in its parts
GEO_RAP_PARTS = ("rap_diagonals", "rap_upload", "rap_device",
                 "rap_download", "rap_assemble")
DEVICE_PIPELINE_PARTS = ("device_pad", "device_upload", "device_build",
                         "hash_weights", "device_download",
                         "device_assemble")


@pytest.fixture
def geo_rap_on(monkeypatch):
    """Run the device Galerkin of geometric aggregation at test sizes."""
    monkeypatch.setattr(aggregation, "_GEO_RAP_MIN_ROWS", 1000)


def _scoped_setup(config: str, n_side: int):
    """Upload and set up under one caller's scope, as a caller timing
    its whole set-up would: ``(caller profile, wall seconds, solver)``."""
    A = poisson_scipy((n_side, n_side, n_side)).tocsr()
    prof = {}
    t0 = time.perf_counter()
    with setup_profile_scope(prof):
        M = SparseMatrix.from_csr(A.indptr, A.indices, A.data)
        s = create_solver(AMGConfig.from_string(config), "default")
        s.setup(M)
    return prof, time.perf_counter() - t0, s


@pytest.mark.parametrize("algorithm", ["aggregation", "classical"])
def test_setup_totals_partition_the_scope(algorithm, geo_rap_on):
    config, n_side, parts = {
        "aggregation": (AGGREGATION, 64, GEO_RAP_PARTS),
        "classical": (CLASSICAL, 20, DEVICE_PIPELINE_PARTS),
    }[algorithm]
    prof, wall, s = _scoped_setup(config, n_side)
    total = sum(prof[k] for k in SETUP_TOTALS)
    assert total == pytest.approx(wall, rel=0.02, abs=1e-3)
    assert all(prof[k] >= 0.0 for k in SETUP_TOTALS), prof
    assert prof["unattributed_s"] < 0.02 * wall, prof
    assert prof["host_s"] > 0 and prof["device_s"] > 0
    assert prof["transfer_s"] > 0
    # the caller's scope holds the upload's phases; the AMG scope keeps
    # its own phase keys, inclusive, and its totals are a part of the
    # caller's
    assert {"validate", "formats", "upload"} <= set(prof)
    amg = s.precond.setup_profile
    for k in SETUP_TOTALS:
        assert 0.0 <= amg[k] <= prof[k] + 1e-9, k
    assert set(parts) <= set(amg), sorted(amg)
    if algorithm == "aggregation":
        assert AGGREGATION_PHASES <= set(amg), sorted(amg)
        inner = sum(amg[k] for k in GEO_RAP_PARTS)
        assert inner <= amg["rap_execute"]
    else:
        assert s.precond.level_builders[0] == "device"
        assert amg["syncs"] > 0
        assert amg["hash_weights"] <= amg["device_build"]


def _host_events(trace_dir):
    from jax.profiler import ProfileData

    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("setup:") or e.name in (
                            "solve", "solve:dispatch"):
                        out.append((e.name, e.duration_ns / 1e9,
                                    dict(e.stats)))
    return out


def test_spans_on_the_profiler_clock(tmp_path, geo_rap_on):
    A = poisson_scipy((16, 16, 16)).tocsr()
    b = poisson_rhs(A.shape[0])
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    prof = {}
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        with setup_profile_scope(prof):
            M = SparseMatrix.from_csr(A.indptr, A.indices, A.data)
            s = create_solver(AMGConfig.from_string(AGGREGATION),
                              "default")
            s.setup(M)
        t0 = time.perf_counter()
        s.solve(b)
        solve_wall = time.perf_counter() - t0
    events = _host_events(tmp_path)
    setup = [e for e in events if e[0].startswith("setup:")]
    assert setup
    for name, _, stats in setup:
        assert stats["kind"] in SPAN_KINDS, (name, stats)
    levels = {stats.get("level") for name, _, stats in setup
              if name == "setup:rap_device"}
    assert levels == {0}
    # each phase's events sum to what the profiles hold for it
    phases = {}
    for name, dur, _ in setup:
        key = name[len("setup:"):]
        phases[key] = phases.get(key, 0.0) + dur
    amg = s.precond.setup_profile
    for key, dur in phases.items():
        held = prof.get(key, 0.0) + amg.get(key, 0.0)
        assert dur == pytest.approx(held, abs=1e-3), key
    (solve,) = [e for e in events if e[0] == "solve"]
    (dispatch,) = [e for e in events if e[0] == "solve:dispatch"]
    assert solve[2]["solver"] == "PCG"
    assert "kind" not in dispatch[2]
    assert dispatch[1] <= solve[1] <= solve_wall
    assert solve[1] == pytest.approx(solve_wall, abs=1e-3)


def test_compile_s_matches_compile_clock(geo_rap_on):
    from benchmark.run import CompileClock

    clock = CompileClock()
    # a size no other test here sets up, so its programs compile
    before = clock.s
    prof, _, _ = _scoped_setup(AGGREGATION, 24)
    compiled = clock.s - before
    assert compiled > 0
    assert prof["compile_s"] == pytest.approx(compiled, rel=0.05)


def test_spans_outside_a_scope_record_nothing():
    from amgx_tpu.core.profiling import setup_phase

    A = poisson_scipy((8, 8, 8)).tocsr()
    with setup_phase("outer", "device"):
        M = SparseMatrix.from_csr(A.indptr, A.indices, A.data)
    assert M.n_rows == 512
    prof = {}
    with setup_profile_scope(prof):
        pass
    assert set(prof) == set(SETUP_TOTALS)
    assert json.loads(json.dumps(prof)) == prof
