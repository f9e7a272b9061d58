"""RCM locality reordering tests.

Reference context: the reference leans on cuSPARSE for arbitrary CSR
(amgx_cusparse.cu); on TPU the equivalent fast path needs column
locality, produced by RCM renumbering at the solver boundary and on
AMG coarse levels (ops/reorder.py).
"""

import numpy as np
import pytest
import scipy.sparse as sps

import amgx_tpu
from amgx_tpu.core.matrix import SparseMatrix
from amgx_tpu.ops import reorder as ro

amgx_tpu.initialize()  # registers the AMG solver


def _scrambled_banded(n, w, bw, seed=0):
    """Banded matrix under a random symmetric permutation: full column
    spread as stored, locality recoverable by RCM."""
    rng = np.random.default_rng(seed)
    r = np.repeat(np.arange(n), w)
    c = np.abs(r + rng.integers(-bw, bw + 1, r.shape))
    c = np.where(c >= n, 2 * (n - 1) - c, c)  # reflect (no boundary pile-up)
    v = rng.standard_normal(r.shape) * 0.1
    m = sps.coo_matrix((v, (r, c)), shape=(n, n)).tocsr()
    m = m + m.T + sps.eye_array(n) * (w * 2.0)  # SPD-ish, symmetric
    p = rng.permutation(n)
    m = m.tocsr()[p][:, p].tocsr()
    m.sort_indices()
    return m


@pytest.fixture
def tiled_env(monkeypatch):
    monkeypatch.setenv("AMGX_TPU_TILED_ELL", "1")


def test_would_build_dia():
    from amgx_tpu.io.poisson import poisson_3d_7pt

    A = poisson_3d_7pt(12, dtype=np.float32)
    assert ro.would_build_dia(A.to_scipy())
    assert not ro.would_build_dia(_scrambled_banded(5000, 4, 300))


def test_maybe_reorder_adopts_on_gain(tiled_env):
    m = _scrambled_banded(6000, 4, 200)
    A = SparseMatrix.from_scipy(m, dtype=np.float32)
    # scrambled: the window spans everything, yet the walk still beats
    # the gather at this size, so it builds
    assert A.ell_wwidth is not None and A.ell_wwidth >= 4096
    A2, perm = ro.maybe_reorder(A, "AUTO")
    assert perm is not None
    assert A2.ell_wwidth is not None
    assert A2.ell_wwidth * 2 <= A.ell_wwidth  # RCM shrank the window
    # permuted system is A[perm][:, perm]
    x = np.random.default_rng(1).standard_normal(6000).astype(np.float32)
    y2 = np.asarray(A2.to_scipy() @ x[perm])
    np.testing.assert_allclose(
        y2, (m @ x)[perm], rtol=1e-4, atol=1e-4
    )


def test_maybe_reorder_adopts_above_wmax(tiled_env):
    """Scrambled, each tile's columns touch nearly every x row of its
    window, a walk above the gate's walk-versus-gather limit: NO
    windowed arrays.  RCM restores them."""
    m = _scrambled_banded(200000, 4, 300, seed=5)
    A = SparseMatrix.from_scipy(m, dtype=np.float32)
    assert A.ell_wcols is None
    A2, perm = ro.maybe_reorder(A, "AUTO")
    assert perm is not None and A2.ell_wcols is not None


def test_maybe_reorder_skips_structured(tiled_env):
    from amgx_tpu.io.poisson import poisson_3d_7pt

    A = poisson_3d_7pt(20, dtype=np.float32)  # DIA, 8000 rows
    _, perm = ro.maybe_reorder(A, "AUTO")
    assert perm is None


def test_maybe_reorder_auto_noop_without_pallas_build():
    """Default CPU backend builds no windowed arrays: AUTO never adopts."""
    m = _scrambled_banded(6000, 4, 200)
    A = SparseMatrix.from_scipy(m, dtype=np.float32)
    _, perm = ro.maybe_reorder(A, "AUTO")
    assert perm is None


def test_reorder_coarse_level_consistency(tiled_env):
    """Folding the coarse permutation into P/R preserves the two-level
    algebra: R2 A P2 == Ac2 and the Galerkin identity is unchanged."""
    n, nc = 6000, 1500
    m = _scrambled_banded(n, 4, 200, seed=3)
    rng = np.random.default_rng(4)
    # simple aggregation P: each fine row -> one coarse column
    agg = rng.integers(0, nc, n)
    P = sps.coo_matrix(
        (np.ones(n), (np.arange(n), agg)), shape=(n, nc)
    ).tocsr()
    R = P.T.tocsr()
    Ac = (R @ m @ P).tocsr()
    P2, R2, Ac2 = ro.reorder_coarse_level(P, R, Ac, np.float32)
    d = (R2 @ m @ P2 - Ac2)
    assert abs(d).max() < 1e-10


def test_nested_solvers_never_reorder(tiled_env):
    """Preconditioners/smoothers receive vectors in the OUTER ordering;
    make_nested must neutralize matrix_reordering for them (only the
    outermost solve() boundary permutes)."""
    from amgx_tpu.config.amg_config import AMGConfig
    from amgx_tpu.solvers import create_solver

    m = _scrambled_banded(5000, 4, 150, seed=9)
    A = SparseMatrix.from_scipy(m, dtype=np.float32)
    b = np.random.default_rng(2).standard_normal(5000).astype(np.float32)
    cfg = AMGConfig.from_string(
        '{"config_version": 2, "solver": {"scope": "main",'
        ' "solver": "PCG", "preconditioner": {"solver": "AMG",'
        ' "scope": "amg", "algorithm": "CLASSICAL", "max_iters": 1,'
        ' "smoother": {"solver": "BLOCK_JACOBI", "scope": "j",'
        ' "monitor_residual": 0}, "min_coarse_rows": 64,'
        ' "coarse_solver": "DENSE_LU_SOLVER", "monitor_residual": 0},'
        ' "max_iters": 120, "tolerance": 1e-7, "monitor_residual": 1}}'
    )
    s = create_solver(cfg, "default")
    s.setup(A)
    assert s._reorder is not None  # outer boundary adopts
    assert s.precond.reordering == "NONE"  # nested: neutralized
    assert s.precond._reorder is None
    for lvl in s.precond.levels[:-1]:
        assert lvl.smoother._reorder is None
    res = s.solve(b)
    x = np.asarray(res.x)
    rel = np.linalg.norm(b - m @ x) / np.linalg.norm(b)
    assert rel < 1e-5


def test_amg_coarse_reorder_respects_none(tiled_env):
    """matrix_reordering=NONE also disables the AMG-internal coarse
    renumbering (reproducible level orderings)."""
    from amgx_tpu.config.amg_config import AMGConfig
    from amgx_tpu.solvers import create_solver

    base = (
        '{"config_version": 2, "solver": {"scope": "main",'
        ' "solver": "AMG", "algorithm": "CLASSICAL", "max_iters": 2,'
        ' "smoother": {"solver": "BLOCK_JACOBI", "scope": "j",'
        ' "monitor_residual": 0}, "min_coarse_rows": 64,'
        ' "coarse_solver": "DENSE_LU_SOLVER", "monitor_residual": 0%s}}'
    )
    s_on = create_solver(AMGConfig.from_string(base % ""), "default")
    s_off = create_solver(
        AMGConfig.from_string(base % ', "matrix_reordering": "NONE"'),
        "default",
    )
    assert s_on.coarse_reorder != "NONE"
    assert s_off.coarse_reorder == "NONE"


def test_solver_boundary_reorder_solution_unchanged(tiled_env):
    """End-to-end: a solver with matrix_reordering adopts RCM internally
    and still returns the solution in the caller's ordering."""
    from amgx_tpu.config.amg_config import AMGConfig
    from amgx_tpu.solvers import create_solver

    m = _scrambled_banded(5000, 4, 150, seed=9)
    A = SparseMatrix.from_scipy(m, dtype=np.float32)
    b = np.random.default_rng(2).standard_normal(5000).astype(np.float32)

    def run(mode):
        cfg = AMGConfig.from_string(
            '{"config_version": 2, "solver": {"scope": "main",'
            ' "solver": "PCG", "preconditioner": {"solver":'
            ' "BLOCK_JACOBI", "scope": "j", "monitor_residual": 0},'
            ' "max_iters": 200, "tolerance": 1e-6,'
            ' "monitor_residual": 1, "matrix_reordering": "%s"}}' % mode
        )
        s = create_solver(cfg, "default")
        s.setup(A)
        return s, s.solve(b)

    s_none, r_none = run("NONE")
    s_auto, r_auto = run("AUTO")
    assert s_none._reorder is None
    assert s_auto._reorder is not None
    assert s_auto.A.ell_wcols is not None
    x_none = np.asarray(r_none.x)
    x_auto = np.asarray(r_auto.x)
    # same linear system, same preconditioner (Jacobi is permutation-
    # equivariant): solutions agree in the caller's ordering
    np.testing.assert_allclose(x_auto, x_none, rtol=2e-3, atol=2e-4)
    rel = np.linalg.norm(b - m @ x_auto) / np.linalg.norm(b)
    assert rel < 1e-5
