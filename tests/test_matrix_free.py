"""MATRIX_FREE stencil-operator tests (ops/stencil.py + the fused
cycle legs): detection (constant / axis-separable / reject), bitwise
SpMV and full-solve parity against the DIA path, fused-vs-unfused
cycle parity with exact trace-time pass counts, values-only
re-derivation (replace_values / serve batching / resetup_entry), and
the store round-trip with the stale-format guardrail.

The load-bearing contract is BITWISE equality: a verified stencil
operator and its fused cycle legs are a pure representation change —
identical arithmetic, identical bits — so every parity assertion here
is tobytes() equality, not allclose.
"""

import numpy as np
import pytest
import scipy.sparse as sps

import amgx_tpu
from amgx_tpu.config.amg_config import AMGConfig
from amgx_tpu.core.errors import StoreError
from amgx_tpu.core.matrix import SparseMatrix
from amgx_tpu.io.poisson import poisson_3d_7pt, poisson_rhs
from amgx_tpu.ops import stencil as st
from amgx_tpu.solvers import create_solver
from amgx_tpu.solvers.base import SUCCESS, Solver

amgx_tpu.initialize()

MF_FORMATS = ("matrix_free", "dia", "dense", "ell")


def _poisson_scipy(n):
    T = sps.diags([-1.0, 2.0, -1.0], [-1, 0, 1], shape=(n, n))
    eye = sps.identity(n)
    A = (
        sps.kron(sps.kron(T, eye), eye)
        + sps.kron(sps.kron(eye, T), eye)
        + sps.kron(sps.kron(eye, eye), T)
    ).tocsr()
    A.sort_indices()
    return A


def _mf_matrix(n=16, dtype=np.float64):
    sp = _poisson_scipy(n).astype(dtype)
    return SparseMatrix.from_scipy(sp, accel_formats=MF_FORMATS), sp


AMG_CFG = """
{"config_version": 2,
 "solver": {"scope": "main", "solver": "AMG", "algorithm": "AGGREGATION",
    "selector": "SIZE_8", "smoother": {"scope": "jac",
        "solver": "BLOCK_JACOBI", "relaxation_factor": 0.8,
        "monitor_residual": 0},
    "presweeps": 1, "postsweeps": 1, "max_levels": 20,
    "min_coarse_rows": 16, "coarse_solver": "DENSE_LU_SOLVER",
    "cycle": "V", "max_iters": 120, "monitor_residual": 1,
    "convergence": "RELATIVE_INI", "tolerance": 1e-08, "norm": "L2",
    "matrix_free": %d, "fused_cycle": %d}}
"""


def _amg_solver(matrix_free, fused, A):
    cfg = AMGConfig.from_string(AMG_CFG % (matrix_free, fused))
    s = create_solver(cfg, "default")
    s.setup(A)
    return s


# ---------------------------------------------------------------------------
# detection


def test_detect_constant_stencil_compresses_dia():
    A, sp = _mf_matrix(16)
    assert A.has_matrix_free
    # the O(nnz) planes are GONE — that is the point
    assert not A.has_dia and A.dia_vals is None
    assert not A.has_ell and not A.has_dense
    meta = A.mf_meta
    assert meta.kind == "const"
    assert meta.grid == (16, 16, 16)
    assert len(meta.offsets) == 7
    coefs = np.sort(np.asarray(A.mf_coefs))
    np.testing.assert_array_equal(coefs, [-1, -1, -1, -1, -1, -1, 6])


def test_detect_axis_separable_stencil():
    """Coefficients that vary only along one axis (a graded-mesh 1D
    metric) detect as kind='axis' with O(nd * L) state."""
    n = 8
    sp = _poisson_scipy(n).astype(np.float64)
    coo = sp.tocoo()
    iz = coo.row // (n * n)
    coo.data = coo.data * (1.0 + iz)
    A = SparseMatrix.from_scipy(coo.tocsr(), accel_formats=MF_FORMATS)
    assert A.has_matrix_free
    assert A.mf_meta.kind == "axis"
    assert A.mf_meta.axis == 2
    assert A.mf_coefs.shape == (7, n)


def test_detect_rejects_jittered_values():
    sp = _poisson_scipy(12)
    rng = np.random.default_rng(0)
    sp = sp.copy()
    sp.data = sp.data + rng.standard_normal(sp.nnz) * 1e-3
    A = SparseMatrix.from_scipy(sp, accel_formats=MF_FORMATS)
    assert not A.has_matrix_free
    assert A.has_dia  # falls back to the next requested format


def test_detect_rejects_non_grid_matrix():
    rng = np.random.default_rng(1)
    m = sps.random(400, 400, density=0.02, random_state=2,
                   format="csr")
    m = (m + m.T + 10 * sps.identity(400)).tocsr()
    m.sort_indices()
    A = SparseMatrix.from_scipy(m, accel_formats=MF_FORMATS)
    assert not A.has_matrix_free


# ---------------------------------------------------------------------------
# SpMV parity (bitwise vs the DIA path)


@pytest.mark.parametrize("dtype", [np.float64, np.float32])
def test_spmv_bitwise_vs_dia(dtype):
    from amgx_tpu.ops.spmv import spmv

    sp = _poisson_scipy(16).astype(dtype)
    A_mf = SparseMatrix.from_scipy(sp, accel_formats=MF_FORMATS)
    A_dia = SparseMatrix.from_scipy(sp, accel_formats=("dia",))
    assert A_mf.has_matrix_free and A_dia.has_dia
    x = np.random.default_rng(3).standard_normal(sp.shape[0])
    x = np.asarray(x, dtype=dtype)
    y_mf = np.asarray(spmv(A_mf, x))
    y_dia = np.asarray(spmv(A_dia, x))
    assert y_mf.tobytes() == y_dia.tobytes()


def test_spmv_axis_bitwise_vs_dia():
    from amgx_tpu.ops.spmv import spmv

    n = 8
    sp = _poisson_scipy(n)
    coo = sp.tocoo()
    coo.data = coo.data * (1.0 + coo.row // (n * n))
    sp2 = coo.tocsr()
    A_mf = SparseMatrix.from_scipy(sp2, accel_formats=MF_FORMATS)
    A_dia = SparseMatrix.from_scipy(sp2, accel_formats=("dia",))
    assert A_mf.mf_meta.kind == "axis" and A_dia.has_dia
    x = np.random.default_rng(4).standard_normal(sp2.shape[0])
    y_mf = np.asarray(spmv(A_mf, x))
    y_dia = np.asarray(spmv(A_dia, x))
    assert y_mf.tobytes() == y_dia.tobytes()


def test_full_solve_bitwise_vs_dia():
    """matrix_free=1 (unfused) must reproduce the DIA reference solve
    bit for bit: same iterates, same residual history, same x."""
    A3 = _poisson_scipy(16)
    b = poisson_rhs(A3.shape[0])
    s_ref = _amg_solver(0, 0, SparseMatrix.from_scipy(A3))
    s_mf = _amg_solver(1, 0, SparseMatrix.from_scipy(A3))
    assert all(lvl.A.has_matrix_free for lvl in s_mf.levels)
    r_ref = s_ref.solve(b)
    r_mf = s_mf.solve(b)
    assert int(r_mf.status) == SUCCESS
    assert int(r_mf.iters) == int(r_ref.iters)
    assert (
        np.asarray(r_mf.x).tobytes() == np.asarray(r_ref.x).tobytes()
    )


def test_galerkin_coarse_levels_stay_matrix_free():
    """Aggregation Galerkin products of a constant stencil on a
    divisible grid are again stencils — the whole hierarchy rides."""
    s = _amg_solver(1, 0, poisson_3d_7pt(16))
    assert len(s.levels) >= 2
    assert all(lvl.A.has_matrix_free for lvl in s.levels)


# ---------------------------------------------------------------------------
# fused cycle legs


def test_fused_cycle_bitwise_and_pass_counts():
    A3 = _poisson_scipy(16)
    b = poisson_rhs(A3.shape[0])
    s_uf = _amg_solver(1, 0, SparseMatrix.from_scipy(A3))
    s_f = _amg_solver(1, 1, SparseMatrix.from_scipy(A3))
    r_uf = s_uf.solve(b)
    r_f = s_f.solve(b)
    assert int(r_f.iters) == int(r_uf.iters)
    assert np.asarray(r_f.x).tobytes() == np.asarray(r_uf.x).tobytes()
    # exact trace-time operator-pass accounting (V, pre=post=1,
    # DenseLU bottom): unfused 3(L-1)+1, fused 2(L-1)+1 — each fused
    # leg is ONE pass instead of three
    L = len(s_uf.levels)
    assert s_uf.cycle_passes_per_iteration() == 3 * (L - 1) + 1
    assert s_f.cycle_passes_per_iteration() == 2 * (L - 1) + 1


def test_fused_noop_without_matrix_free():
    """fused_cycle=1 with matrix_free=0: no matrix-free levels, so no
    legs fuse and the pass count stays the reference count."""
    A3 = _poisson_scipy(16)
    b = poisson_rhs(A3.shape[0])
    s_ref = _amg_solver(0, 0, SparseMatrix.from_scipy(A3))
    s_f = _amg_solver(0, 1, SparseMatrix.from_scipy(A3))
    L = len(s_ref.levels)
    assert s_f.cycle_passes_per_iteration() == 3 * (L - 1) + 1
    r_ref = s_ref.solve(b)
    r_f = s_f.solve(b)
    assert np.asarray(r_f.x).tobytes() == np.asarray(r_ref.x).tobytes()


def test_cycle_passes_feed_solver_telemetry():
    from amgx_tpu.telemetry import registry as treg

    reg = treg.TelemetryRegistry()
    old = treg._REGISTRY
    treg._REGISTRY = reg
    try:
        A3 = _poisson_scipy(16)
        cfg_text = AMG_CFG % (1, 1)
        cfg_text = cfg_text.replace(
            '"matrix_free"', '"obtain_timings": 1, "matrix_free"'
        )
        s = create_solver(AMGConfig.from_string(cfg_text), "default")
        s.setup(SparseMatrix.from_scipy(A3))
        res = s.solve(poisson_rhs(A3.shape[0]))
        L = len(s.levels)
        snap = reg.snapshot()["solvers"]["data"]
        (stats,) = [v for k, v in snap.items() if "AMG" in k.upper()]
        assert stats["cycle_passes"] == (2 * (L - 1) + 1) * int(
            res.iters
        )
        text = reg.render_prometheus()
        assert "amgx_solver_cycle_passes_total" in text
    finally:
        treg._REGISTRY = old


# ---------------------------------------------------------------------------
# values-only re-derivation (replace_values / astype)


def test_replace_values_rederives_coefficients():
    from amgx_tpu.ops.spmv import spmv

    A, sp = _mf_matrix(12)
    v2 = np.asarray(sp.data) * 1.7
    A2 = A.replace_values(v2)
    assert A2.has_matrix_free and A2.mf_meta == A.mf_meta
    x = np.random.default_rng(5).standard_normal(A.n_rows)
    y = np.asarray(spmv(A2, x))
    ref = np.asarray(
        spmv(SparseMatrix.from_scipy(
            sps.csr_matrix(
                (v2, sp.indices, sp.indptr), shape=sp.shape
            ), accel_formats=("dia",),
        ), x)
    )
    assert y.tobytes() == ref.tobytes()


def test_astype_keeps_matrix_free():
    A, _ = _mf_matrix(12)
    A32 = A.astype(np.float32)
    assert A32.has_matrix_free
    assert np.asarray(A32.mf_coefs).dtype == np.float32


# ---------------------------------------------------------------------------
# serve: vmapped batch groups + resetup_entry


def _scaled_family(n, count, seed=0):
    """Systems sharing the Poisson pattern, each a constant multiple
    of the stencil (so every instance stays a verified stencil)."""
    sp = _poisson_scipy(n)
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(count):
        c = float(rng.uniform(0.5, 2.0))
        m = sp.copy()
        m.data = m.data * c
        out.append((m, rng.standard_normal(sp.shape[0])))
    return out


# the vmapped serve batch path needs the planned Galerkin rebuild
SERVE_CFG = (AMG_CFG % (1, 1)).replace(
    '"matrix_free"', '"structure_reuse_levels": -1, "matrix_free"'
)


@pytest.mark.serve
def test_batched_group_parity_matrix_free():
    """A vmapped serve group over matrix-free hierarchies must match
    the sequential resetup reference iteration-for-iteration (the
    make_batch_params values-only path re-derives mf_coefs on device
    through the same gather replace_values uses)."""
    from amgx_tpu.serve import BatchedSolveService

    systems = _scaled_family(16, 5, seed=7)
    svc = BatchedSolveService(config=SERVE_CFG, max_batch=8)
    results = svc.solve_many(systems)
    m = svc.metrics.snapshot()
    assert m["batches"] == 1
    assert m.get("fallback_solves", 0) == 0
    # the cached template hierarchy actually rides MATRIX_FREE
    (pat,) = svc._patterns.values()
    entry = svc.cache.peek(pat.fingerprint, svc.cfg_key,
                           np.dtype(np.float64))
    amg = entry.solver
    assert all(lvl.A.has_matrix_free for lvl in amg.levels)
    s = create_solver(AMGConfig.from_string(SERVE_CFG), "default")
    s.setup(SparseMatrix.from_scipy(systems[0][0],
                                    accel_formats=MF_FORMATS))
    for (m2, b), r in zip(systems, results):
        s.resetup(SparseMatrix.from_scipy(m2,
                                          accel_formats=MF_FORMATS))
        ref = s.solve(b)
        assert int(r.status) == 0
        assert int(r.iters) == int(ref.iters)
        ref_x = np.asarray(ref.x)
        err = np.linalg.norm(np.asarray(r.x) - ref_x) / max(
            np.linalg.norm(ref_x), 1e-300
        )
        assert err < 1e-9


@pytest.mark.serve
def test_bytes_by_format_reports_compression():
    from amgx_tpu.serve import BatchedSolveService

    systems = _scaled_family(16, 2, seed=8)
    svc = BatchedSolveService(config=SERVE_CFG, max_batch=4)
    svc.solve_many(systems)
    by_fmt = svc.cache.bytes_by_format()
    assert by_fmt.get("MATRIX_FREE", 0) > 0
    assert by_fmt.get("DIA", 0) == 0
    snap = svc.telemetry_snapshot()
    assert snap["hierarchy_format_bytes"] == by_fmt


@pytest.mark.serve
def test_resetup_entry_rederives_stencil_state():
    from amgx_tpu.serve import BatchedSolveService

    systems = _scaled_family(16, 1, seed=9)
    A0, b = systems[0]
    svc = BatchedSolveService(config=SERVE_CFG, max_batch=4)
    res = svc.solve_many([(A0, b)])
    assert int(res[0].status) == 0
    raw_fp = getattr(A0, "_amgx_tpu_fp")
    v1 = np.asarray(A0.data) * 3.0
    assert svc.resetup_entry(raw_fp, v1) is None
    pat = svc._patterns[raw_fp]
    entry = svc.cache.peek(pat.fingerprint, svc.cfg_key,
                           np.dtype(np.float64))
    A = entry.solver.levels[0].A
    assert A.has_matrix_free
    # compact state re-derived from the new values via the gather map
    got = np.asarray(A.mf_coefs)
    want = v1[np.asarray(A.mf_src)]
    assert got.tobytes() == want.tobytes()


# ---------------------------------------------------------------------------
# store round-trip + stale-format guardrail


def test_store_roundtrip_matrix_free(tmp_path):
    A3 = _poisson_scipy(16)
    b = poisson_rhs(A3.shape[0])
    s = _amg_solver(1, 1, SparseMatrix.from_scipy(A3))
    res1 = s.solve(b)
    path = tmp_path / "mf.npz"
    s.save_setup(path)
    s2 = Solver.load_setup(path)
    assert s2.setup_stats["restored"] is True
    assert all(lvl.A.has_matrix_free for lvl in s2.levels)
    for l1, l2 in zip(s.levels, s2.levels):
        assert l2.A.mf_meta == l1.A.mf_meta
        assert (
            np.asarray(l2.A.mf_coefs).tobytes()
            == np.asarray(l1.A.mf_coefs).tobytes()
        )
    res2 = s2.solve(b)
    assert int(res2.iters) == int(res1.iters)
    assert (
        np.asarray(res2.x).tobytes() == np.asarray(res1.x).tobytes()
    )


def test_stale_dia_artifact_rejected_under_matrix_free(
    tmp_path, monkeypatch
):
    """A payload written by a pre-MATRIX_FREE writer (config says
    matrix_free=1 but the levels store DIA planes for a verifiable
    stencil) is stale: restore re-runs detection and refuses."""
    from amgx_tpu.amg.hierarchy import AMGSolver

    # simulate the old writer: same config, detection never runs
    monkeypatch.setattr(
        AMGSolver, "_maybe_matrix_free", lambda self, A: A
    )
    monkeypatch.setattr(
        AMGSolver, "_accel_formats",
        lambda self: ("dia", "dense", "ell"),
    )
    s = _amg_solver(1, 0, poisson_3d_7pt(16))
    assert not any(lvl.A.has_matrix_free for lvl in s.levels)
    path = tmp_path / "stale.npz"
    s.save_setup(path)
    monkeypatch.undo()
    with pytest.raises(StoreError):
        Solver.load_setup(path)


def test_matrix_free_artifact_rejected_when_knob_off():
    s = _amg_solver(1, 0, poisson_3d_7pt(16))
    assert any(lvl.A.has_matrix_free for lvl in s.levels)
    s.matrix_free = False  # the restoring config's view of the knob
    with pytest.raises(StoreError):
        s._check_restored_formats()
