"""Windowed ELL Pallas kernel tests (interpret mode on CPU).

Reference parity: cuSPARSE bsrmv (amgx_cusparse.cu:49-102) for
unstructured matrices with column locality — the hot gather-bound case
is AMG coarse Galerkin operators, which setup renumbers for locality.
Sizes sit above the dense-acceleration cutoff (4096 rows) so the ELL
structures are actually built.
"""

import jax
import numpy as np
import pytest
import scipy.sparse as sps

from amgx_tpu.core.matrix import SparseMatrix
from amgx_tpu.ops import pallas_well as pw


def _banded_random(n, w, bw, seed=7):
    """Random matrix whose columns stay within +-bw of the diagonal."""
    rng = np.random.default_rng(seed)
    r = np.repeat(np.arange(n), w)
    c = np.clip(r + rng.integers(-bw, bw + 1, r.shape), 0, n - 1)
    v = rng.standard_normal(r.shape)
    m = sps.coo_matrix((v, (r, c)), shape=(n, n)).tocsr()
    m.sum_duplicates()
    m.sort_indices()
    return m


@pytest.fixture
def tiled_env(monkeypatch):
    monkeypatch.setenv("AMGX_TPU_TILED_ELL", "1")


def test_tile_ell_layout():
    cols = np.arange(12, dtype=np.int64).reshape(6, 2)
    vals = np.arange(12, dtype=np.float64).reshape(6, 2)
    tc, tv = pw.tile_ell(cols, vals)
    assert tc.shape == (1, 8, 2 * 128)
    # row r, slot k lives at lane k*128 + r of sublane r//128 (here 0)
    assert tc[0, 0, 0 * 128 + 3] == cols[3, 0]
    assert tc[0, 0, 1 * 128 + 3] == cols[3, 1]
    assert tv[0, 0, 1 * 128 + 5] == vals[5, 1]
    # padding rows are zero
    assert tv[0, 0, 0 * 128 + 6] == 0.0


def test_build_windowed_basic(tiled_env):
    m = _banded_random(6000, 5, 300)
    A = SparseMatrix.from_scipy(m, dtype=np.float32)
    assert A.ell_wcols is not None and A.ell_wwidth is not None
    assert A.ell_wwidth % 128 == 0
    # local ids in range
    assert int(np.asarray(A.ell_wcols).max()) < A.ell_wwidth
    # window bases lane-aligned
    assert np.all(np.asarray(A.ell_wbase) % 128 == 0)


def test_no_window_when_no_locality(tiled_env):
    """Columns spread over the whole matrix: every tile touches nearly
    every x row of its window, so the kernel's walk costs more than the
    XLA gather and the matrix rides the plain ELL arrays."""
    rng = np.random.default_rng(3)
    n = 200000
    m = sps.random(n, n, density=4e-5, random_state=rng,
                   format="csr") + sps.eye_array(n) * 3.0
    m = m.tocsr()
    m.sort_indices()
    A = SparseMatrix.from_scipy(m, dtype=np.float32)
    assert A.has_ell
    assert A.ell_wcols is None and A.ell_wcount is None


def _ell_of(m):
    from amgx_tpu.core.matrix import _build_ell_np

    m = m.tocsr()
    w = int(np.diff(m.indptr).max())
    cols, vals, _ = _build_ell_np(m.indptr, m.indices,
                                  m.data.astype(np.float32), m.shape[0], w, 1)
    return m.indptr, cols, vals


def test_gate_declines_walk_dearer_than_gather():
    """The gate's two estimates decide: a matrix whose tiles touch most
    of a wide window is tiled, but not admitted."""
    rng = np.random.default_rng(4)
    n = 200000
    m = sps.random(n, n, density=4e-5, random_state=rng, format="csr")
    m = (m + sps.eye_array(n)).tocsr()
    ro, cols, vals = _ell_of(m)
    we = pw.tile_windowed_ell(ro, cols, vals)
    assert we is not None  # the window fits VMEM
    kernel_s, gather_s = pw.well_estimate_s(we, n)
    assert pw._KERNEL_MARGIN * kernel_s >= gather_s
    assert pw.build_windowed_ell(ro, cols, vals) is None


def test_gate_declines_window_over_vmem_budget():
    """Two bands far apart: few touched rows per tile, so the walk is
    cheap, but the window is wider than the kernel's VMEM budget."""
    n, far = 5000, pw._WINDOW_MAX_LANES + 4096
    r = np.repeat(np.arange(n), 2)
    c = np.stack([np.arange(n), np.arange(n) + far], axis=1).ravel()
    m = sps.csr_matrix((np.ones(2 * n), (r, c)), shape=(n, n + far))
    ro, cols, vals = _ell_of(m)
    assert pw.tile_windowed_ell(ro, cols, vals) is None
    assert pw.build_windowed_ell(ro, cols, vals) is None


def test_touched_rows_lists(tiled_env):
    """Each tile lists, sorted, exactly the window rows its real entries
    fall in; padding slots add none."""
    m = _banded_random(6000, 5, 300)
    ro, cols, vals = _ell_of(m)
    we = pw.build_windowed_ell(ro, cols, vals)
    assert we is not None
    m = m.tocsr()
    rows_of = np.repeat(np.arange(6000), np.diff(m.indptr))
    for t in range(we.bases.shape[0]):
        sel = rows_of // pw._ROW_TILE == t
        want = np.unique((m.indices[sel] - we.bases[t]) // pw._LANE)
        got = we.rows[t, 0, : we.counts[t]]
        np.testing.assert_array_equal(got, want)
        assert np.all(we.rows[t, 0, we.counts[t]:] == 0)


def _two_band(n, far):
    """Each row holds its diagonal and an entry about ``far`` columns
    away (jittered, so not DIA; wrapped): a window over 16,384 lanes,
    a few x rows a tile."""
    rng = np.random.default_rng(8)
    r = np.repeat(np.arange(n), 2)
    off = far + rng.integers(0, 256, n)
    c = np.stack([np.arange(n), (np.arange(n) + off) % n], axis=1).ravel()
    m = sps.csr_matrix((rng.standard_normal(2 * n), (r, c)), shape=(n, n))
    m.sum_duplicates()
    m.sort_indices()
    return m


def test_windowed_two_band_wide_window_interpret(tiled_env):
    """A window far over 16,384 lanes whose tiles touch few x rows
    takes the kernel and matches scipy."""
    n = 60000
    m = _two_band(n, 30000)
    A = SparseMatrix.from_scipy(m, dtype=np.float32)
    assert A.ell_wwidth is not None and A.ell_wwidth > 16384
    assert int(np.asarray(A.ell_wcount).max()) <= 20
    x = np.random.default_rng(5).standard_normal(n).astype(np.float32)
    y = pw.pallas_well_spmv(A, x, interpret=True)
    np.testing.assert_allclose(np.asarray(y), m @ x, rtol=2e-4, atol=2e-4)


def _aggregation_p(n_fine, n_coarse, seed):
    """A classical-P-shaped matrix: each fine row interpolates from up
    to 3 coarse columns near its own position."""
    rng = np.random.default_rng(seed)
    r = np.repeat(np.arange(n_fine), 3)
    centre = r * n_coarse // n_fine
    c = np.clip(centre + rng.integers(-4, 5, r.shape), 0, n_coarse - 1)
    v = rng.standard_normal(r.shape)
    m = sps.coo_matrix((v, (r, c)), shape=(n_fine, n_coarse)).tocsr()
    m.sum_duplicates()
    m.sort_indices()
    return m


@pytest.mark.parametrize("shape", ["P", "R"])
def test_windowed_rectangular_interpret(tiled_env, shape):
    """A prolongation (rows >> cols) and its restriction (cols >>
    rows) take the kernel and match scipy."""
    p = _aggregation_p(20000, 5200, seed=12)
    m = p if shape == "P" else p.T.tocsr()
    A = SparseMatrix.from_scipy(m, dtype=np.float32)
    assert A.ell_wcols is not None
    x = np.random.default_rng(13).standard_normal(m.shape[1]).astype(
        np.float32)
    y = pw.pallas_well_spmv(A, x, interpret=True)
    np.testing.assert_allclose(np.asarray(y), m @ x, rtol=2e-4, atol=2e-4)


def test_windowed_empty_tiles_interpret(tiled_env):
    """Whole tiles without an entry walk no rows and give zeros; the
    tiles around them still match scipy."""
    n = 8192
    keep = (np.arange(n) // pw._ROW_TILE) % 2 == 0  # tiles 1, 3, ... empty
    m = _banded_random(n, 4, 100, seed=14).tocsr()
    m = sps.diags(keep.astype(np.float64)) @ m
    m = m.tocsr()
    m.eliminate_zeros()
    A = SparseMatrix.from_scipy(m, dtype=np.float32)
    assert A.ell_wcols is not None
    counts = np.asarray(A.ell_wcount)
    assert np.all(counts[1::2] == 0) and np.all(counts[::2] > 0)
    x = np.random.default_rng(15).standard_normal(n).astype(np.float32)
    y = np.asarray(pw.pallas_well_spmv(A, x, interpret=True))
    np.testing.assert_allclose(y, m @ x, rtol=2e-4, atol=2e-4)
    assert np.all(y[~keep] == 0)


def test_windowed_spmv_interpret(tiled_env):
    m = _banded_random(6000, 6, 500, seed=11)
    A = SparseMatrix.from_scipy(m, dtype=np.float32)
    assert A.ell_wcols is not None
    x = np.random.default_rng(5).standard_normal(6000).astype(np.float32)
    y = pw.pallas_well_spmv(A, x, interpret=True)
    np.testing.assert_allclose(
        np.asarray(y), m @ x, rtol=2e-4, atol=2e-4
    )


def test_vmap_runs_plain_ell_gather(tiled_env):
    """One instance traces the kernel; under jax.vmap (serve's batched
    solves) the plain ELL arrays' XLA gather runs instead."""
    m = _banded_random(6000, 6, 500, seed=11)
    A = SparseMatrix.from_scipy(m, dtype=np.float32)
    xs = np.random.default_rng(6).standard_normal((2, 6000)).astype(
        np.float32)

    def one(x):
        return pw.pallas_well_spmv(A, x, interpret=True)

    assert "pallas_call" in str(jax.make_jaxpr(one)(xs[0]))
    assert "pallas_call" not in str(jax.make_jaxpr(jax.vmap(one))(xs))
    np.testing.assert_allclose(
        np.asarray(jax.vmap(one)(xs)), (m @ xs.T).T, rtol=2e-4, atol=2e-4
    )


def test_windowed_empty_rows_interpret(tiled_env):
    """Rows with no entries and a ragged final tile."""
    n = 5100
    rng = np.random.default_rng(9)
    r = np.repeat(np.arange(0, n, 3), 2)
    c = np.clip(r + rng.integers(-40, 41, r.shape), 0, n - 1)
    v = rng.standard_normal(r.shape)
    m = sps.coo_matrix((v, (r, c)), shape=(n, n)).tocsr()
    m.sum_duplicates()
    m.sort_indices()
    A = SparseMatrix.from_scipy(m, dtype=np.float32)
    assert A.ell_wcols is not None
    x = rng.standard_normal(n).astype(np.float32)
    y = pw.pallas_well_spmv(A, x, interpret=True)
    np.testing.assert_allclose(np.asarray(y), m @ x, rtol=2e-4, atol=2e-4)


def test_replace_values_keeps_touched_rows(tiled_env):
    """A values-only refresh of a wide-window matrix keeps its touched-
    row lists (the structure) and the kernel reads the new values."""
    n = 40000
    m = _two_band(n, 20000)
    A = SparseMatrix.from_scipy(m, dtype=np.float32)
    assert A.ell_wrows is not None
    A2 = A.replace_values(np.asarray(A.values) * 3.0)
    assert A2.ell_wrows is A.ell_wrows and A2.ell_wcount is A.ell_wcount
    x = np.random.default_rng(16).standard_normal(n).astype(np.float32)
    y = pw.pallas_well_spmv(A2, x, interpret=True)
    np.testing.assert_allclose(
        np.asarray(y), 3.0 * (m @ x), rtol=2e-4, atol=2e-4
    )


def test_replace_values_refreshes_windowed(tiled_env):
    m = _banded_random(5200, 4, 200, seed=2)
    A = SparseMatrix.from_scipy(m, dtype=np.float32)
    assert A.ell_wvals is not None
    A2 = A.replace_values(np.asarray(A.values) * -0.5)
    x = np.random.default_rng(1).standard_normal(5200).astype(np.float32)
    y = pw.pallas_well_spmv(A2, x, interpret=True)
    np.testing.assert_allclose(
        np.asarray(y), -0.5 * (m @ x), rtol=2e-4, atol=2e-4
    )


def test_cpu_backend_skips_windowed_build():
    """Without the env override, CPU builds no windowed arrays and the
    dispatcher stays on the XLA path."""
    m = _banded_random(5000, 5, 300)
    A = SparseMatrix.from_scipy(m, dtype=np.float32)
    assert A.ell_wcols is None
    assert not pw.well_kernel_selected(A)


def test_cpu_backend_not_supported(tiled_env, monkeypatch):
    """The gate selects the kernel on a TPU backend only: windowed
    arrays alone (forced here) keep the CPU on the XLA path."""
    m = _banded_random(5000, 5, 300)
    A = SparseMatrix.from_scipy(m, dtype=np.float32)
    assert A.ell_wcols is not None
    assert not pw.well_kernel_selected(A)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert pw.well_kernel_selected(A)


_CLASSICAL_PCG = """
{"config_version": 2,
 "solver": {"scope": "main", "solver": "PCG", "max_iters": 100,
    "tolerance": 1e-6, "monitor_residual": 1,
    "preconditioner": {"scope": "amg", "solver": "AMG",
       "algorithm": "CLASSICAL", "selector": "PMIS", "interpolator": "D1",
       "smoother": {"scope": "j", "solver": "BLOCK_JACOBI",
           "relaxation_factor": 0.8, "monitor_residual": 0},
       "presweeps": 1, "postsweeps": 1, "max_levels": 5,
       "min_coarse_rows": 64, "coarse_solver": "DENSE_LU_SOLVER",
       "cycle": "V", "max_iters": 1, "monitor_residual": 0}}}
"""


@pytest.mark.parametrize("tiled", ["1", "0"])
def test_setup_profile_counts_windowed_matrices(monkeypatch, tiled):
    """``well_matrices`` counts the hierarchy's A, P and R that took
    the windowed arrays, and ``well_touched_rows`` holds each one's
    mean touched x rows per tile under ``l<level>.<role>``; the Krylov
    wrapper's merged profile carries both."""
    import amgx_tpu
    from amgx_tpu.config.amg_config import AMGConfig
    from amgx_tpu.io.poisson import poisson_3d_7pt
    from amgx_tpu.solvers import create_solver

    monkeypatch.setenv("AMGX_TPU_TILED_ELL", tiled)
    amgx_tpu.initialize()
    s = create_solver(AMGConfig.from_string(_CLASSICAL_PCG), "default")
    s.setup(poisson_3d_7pt(32, dtype=np.float32))
    amg = s.precond
    touched = amg.setup_profile["well_touched_rows"]
    want = {
        f"l{lvl.level_id}.{role}": float(np.asarray(M.ell_wcount).mean())
        for lvl in amg.levels
        for role, M in (("A", lvl.A), ("P", lvl.P), ("R", lvl.R))
        if M is not None and M.ell_wcols is not None
    }
    assert touched == want
    assert amg.setup_profile["well_matrices"] == len(want)
    if tiled == "1":
        assert "l0.P" in want and all(v > 0 for v in want.values())
    else:
        assert want == {}
    merged = s.collect_setup_profile()
    assert merged["well_touched_rows"] == touched
