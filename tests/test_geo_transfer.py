"""Structured grid transfers of geometric aggregation
(ops/geo_transfer.py): SpMV parity with the CSR twins, the bit-for-bit
check that admits a P or R to the format, the AMG solve with and
without it, and the paths that must carry it (dtype casts, the store,
the batched serve path) or must not take it (classical, matching,
row-sharded hierarchies)."""

import jax
import numpy as np
import pytest
import scipy.sparse as sps

import amgx_tpu
from amgx_tpu.amg.aggregation import build_aggregation_level
from amgx_tpu.config.amg_config import AMGConfig
from amgx_tpu.core.matrix import SparseMatrix
from amgx_tpu.io.poisson import poisson_rhs, poisson_scipy
from amgx_tpu.ops import geo_transfer as gt
from amgx_tpu.ops.spmv import spmv
from amgx_tpu.solvers import create_solver

amgx_tpu.initialize()

_EPS32 = float(np.finfo(np.float32).eps)


def _amg_cfg(selector="SIZE_8", algorithm="AGGREGATION", extra=""):
    return AMGConfig.from_string(
        '{"config_version": 2, "solver": {"scope": "main",'
        ' "solver": "PCG", "max_iters": 100, "tolerance": 1e-6,'
        ' "monitor_residual": 1, "convergence": "RELATIVE_INI",'
        ' "preconditioner": {"scope": "amg", "solver": "AMG",'
        f' "algorithm": "{algorithm}", "selector": "{selector}",'
        ' "smoother": {"scope": "j", "solver": "BLOCK_JACOBI",'
        ' "relaxation_factor": 0.8, "monitor_residual": 0},'
        ' "presweeps": 1, "postsweeps": 1, "max_iters": 1,'
        ' "min_coarse_rows": 64, "max_levels": 10,'
        f' "coarse_solver": "DENSE_LU_SOLVER", "cycle": "V"{extra},'
        ' "monitor_residual": 0}}}'
    )


def _stencil(grid, coupling=(1.0, 1.0, 1.0)):
    """7-point operator on ``grid`` (x fastest) with per-axis
    couplings; diagonally dominant, float32."""
    nx, ny, nz = grid
    n = nx * ny * nz
    i = np.arange(n)
    ix, iy, iz = i % nx, (i // nx) % ny, i // (nx * ny)
    rows, cols, vals = [i], [i], [np.full(n, 2.0 * sum(coupling) + 0.1)]
    for (dx, dy, dz), c in (((1, 0, 0), coupling[0]),
                            ((0, 1, 0), coupling[1]),
                            ((0, 0, 1), coupling[2])):
        for s in (1, -1):
            ok = ((ix + s * dx >= 0) & (ix + s * dx < nx)
                  & (iy + s * dy >= 0) & (iy + s * dy < ny)
                  & (iz + s * dz >= 0) & (iz + s * dz < nz))
            j = i + s * (dx + nx * dy + nx * ny * dz)
            rows.append(i[ok])
            cols.append(j[ok])
            vals.append(np.full(int(ok.sum()), -c))
    A = sps.csr_matrix(
        (np.concatenate(vals), (np.concatenate(rows),
                                np.concatenate(cols))),
        shape=(n, n), dtype=np.float32,
    )
    A.sort_indices()
    return A


def _level(grid, selector, coupling=(1.0, 1.0, 1.0)):
    """(P, R, geo_info) of the first aggregation level on ``grid``."""
    P, R, _, geo = build_aggregation_level(
        _stencil(grid, coupling), _amg_cfg(selector), "amg"
    )
    return P.astype(np.float32), R.astype(np.float32), geo


def _assert_structured(M, transpose):
    assert M.has_geo_transfer and M.geo_meta.transpose is transpose
    # the grid reductions replace every general format
    assert not (M.has_ell or M.has_dense or M.has_dia)


def _parity(P, R, geo, rng):
    Pm = SparseMatrix.from_scipy(P, geo_transfer=geo)
    Rm = SparseMatrix.from_scipy(R, geo_transfer=geo)
    _assert_structured(Pm, False)
    _assert_structured(Rm, True)
    Pc = SparseMatrix.from_scipy(P, build_ell=False)
    Rc = SparseMatrix.from_scipy(R, build_ell=False)
    n, nc = P.shape
    x = rng.standard_normal(n).astype(np.float32)
    xc = rng.standard_normal(nc).astype(np.float32)
    y, y_ref = np.asarray(spmv(Rm, x)), np.asarray(spmv(Rc, x))
    np.testing.assert_allclose(
        y, y_ref, rtol=4 * _EPS32,
        atol=4 * _EPS32 * float(np.abs(y_ref).max()),
    )
    z, z_ref = np.asarray(spmv(Pm, xc)), np.asarray(spmv(Pc, xc))
    np.testing.assert_allclose(z, z_ref, rtol=_EPS32, atol=0)
    return Pm, Rm


@pytest.mark.parametrize(
    "grid,selector",
    [((8, 8, 8), s) for s in ("SIZE_2", "SIZE_4", "SIZE_8")]
    + [((13, 13, 13), s) for s in ("SIZE_2", "SIZE_4", "SIZE_8")]
    + [((12, 10, 6), s) for s in ("SIZE_2", "SIZE_4", "SIZE_8")],
)
def test_structured_transfer_matches_csr(grid, selector):
    P, R, geo = _level(grid, selector)
    assert geo is not None and tuple(geo[0]) == grid
    _parity(P, R, geo, np.random.default_rng(7))


def test_anisotropic_blocks_semicoarsen_and_match_csr():
    """A stencil strong along x aggregates 8x1x1 boxes: block and grid
    are parameters of the format, not a 2x2x2 assumption."""
    P, R, geo = _level((32, 6, 4), "SIZE_8", coupling=(1.0, 1e-3, 1e-3))
    assert tuple(geo[1]) == (8, 1, 1)
    _parity(P, R, geo, np.random.default_rng(8))


def test_wide_grid_chunks_the_x_step():
    """Rows wider than one MXU chunk (and not a whole number of them)
    are padded to whole chunks and sliced back."""
    grid, block = (600, 3, 2), (2, 1, 1)
    n = 600 * 3 * 2
    agg = gt.blocked_map(grid, block)
    P = sps.csr_matrix(
        (np.ones(n, np.float32), (np.arange(n), agg)),
        shape=(n, int(agg.max()) + 1),
    )
    _parity(P, P.T.tocsr(), (grid, block), np.random.default_rng(9))


def test_weighted_transfer_keeps_the_weights():
    """P's values enter as one fine-length weight vector, on both P and
    R, so the format keeps P's mathematics when they are not ones."""
    P, R, geo = _level((12, 10, 6), "SIZE_8")
    w = np.random.default_rng(3).uniform(0.5, 1.5, P.shape[0])
    P = sps.csr_matrix(
        (w.astype(np.float32), P.indices, P.indptr), shape=P.shape
    )
    Pm, Rm = _parity(P, P.T.tocsr(), geo, np.random.default_rng(4))
    np.testing.assert_array_equal(np.asarray(Pm.geo_w), P.data)
    np.testing.assert_array_equal(np.asarray(Rm.geo_w), P.data)
    # a values-only swap re-derives the weights on both sides
    w2 = (2.0 * w).astype(np.float32)
    R2 = Rm.replace_values(w2[np.asarray(Rm.col_indices)])
    P2 = Pm.replace_values(w2)
    np.testing.assert_array_equal(np.asarray(P2.geo_w), w2)
    np.testing.assert_array_equal(np.asarray(R2.geo_w), w2)
    x = np.random.default_rng(5).standard_normal(P.shape[0])
    x = x.astype(np.float32)
    np.testing.assert_allclose(
        np.asarray(spmv(R2, x)), 2.0 * np.asarray(spmv(Rm, x)),
        rtol=4 * _EPS32, atol=1e-5,
    )


def test_unit_weights_need_no_vector():
    P, R, geo = _level((8, 8, 8), "SIZE_8")
    assert SparseMatrix.from_scipy(P, geo_transfer=geo).geo_w is None
    assert SparseMatrix.from_scipy(R, geo_transfer=geo).geo_w is None


def _refused(P, R, geo):
    for M in (P, R):
        A = SparseMatrix.from_scipy(M, geo_transfer=geo)
        assert not A.has_geo_transfer
        # today's formats: ELL or dense by size
        assert A.has_ell or A.has_dense


@pytest.mark.parametrize("kind", ["swapped_columns", "rcm", "shifted"])
def test_check_refuses_other_transfers(kind):
    P, R, geo = _level((12, 10, 6), "SIZE_8")
    nc = P.shape[1]
    if kind == "swapped_columns":
        perm = np.arange(nc)
        perm[[0, 1]] = perm[[1, 0]]
    elif kind == "rcm":
        from amgx_tpu.ops.reorder import rcm_permutation

        _, _, Ac, _ = build_aggregation_level(
            _stencil((12, 10, 6)), _amg_cfg(), "amg"
        )
        perm = rcm_permutation(Ac)
        assert not np.array_equal(perm, np.arange(nc))
    else:
        # the same boxes numbered from a shifted origin
        perm = np.roll(np.arange(nc), 1)
    P2 = P.tocsr()[:, perm].tocsr()
    P2.sort_indices()
    R2 = R.tocsr()[perm, :].tocsr()
    R2.sort_indices()
    _refused(P2, R2, geo)


def test_check_refuses_a_wrong_grid_or_block():
    P, R, geo = _level((12, 10, 6), "SIZE_8")
    grid, block = geo
    for other in (((10, 12, 6), block), (grid, (2, 2, 1))):
        assert gt.detect_geo_transfer_np(
            P.indptr, P.indices, P.data, *P.shape, *other
        ) is None
        assert gt.detect_geo_transfer_np(
            R.indptr, R.indices, R.data, *R.shape, *other
        ) is None


def _pcg_amg(A, cfg=None):
    s = create_solver(cfg or _amg_cfg(), "default")
    s.setup(A)
    return s


def test_pcg_amg_same_iterations_as_general_formats(monkeypatch):
    A = SparseMatrix.from_scipy(poisson_scipy((32, 32, 32)),
                                dtype=np.float32)
    b = poisson_rhs(A.n_rows, dtype=np.float32)
    s = _pcg_amg(A)
    amg = s.precond
    assert len(amg.levels) >= 3
    assert amg.setup_profile["geo_transfer_levels"] == len(amg.levels) - 1
    for lvl in amg.levels[:-1]:
        _assert_structured(lvl.P, False)
        _assert_structured(lvl.R, True)
    res = s.solve(b)

    # the same hierarchy with the format disabled inside the test
    monkeypatch.setattr(gt, "detect_geo_transfer_np",
                        lambda *a, **k: None)
    s_ref = _pcg_amg(A)
    assert s_ref.precond.setup_profile["geo_transfer_levels"] == 0
    assert all(not lvl.P.has_geo_transfer
               for lvl in s_ref.precond.levels[:-1])
    ref = s_ref.solve(b)
    assert bool(res.converged) and bool(ref.converged)
    assert int(res.iters) == int(ref.iters)
    np.testing.assert_allclose(
        np.asarray(res.x), np.asarray(ref.x), rtol=0,
        atol=1e-5 * float(np.abs(np.asarray(ref.x)).max()),
    )


def test_bf16_cast_keeps_the_format():
    P, R, geo = _level((12, 10, 6), "SIZE_8")
    w = np.random.default_rng(5).uniform(0.5, 1.5, P.shape[0])
    P = sps.csr_matrix(
        (w.astype(np.float32), P.indices, P.indptr), shape=P.shape
    )
    import jax.numpy as jnp

    for M in (P, P.T.tocsr()):
        A = SparseMatrix.from_scipy(M, geo_transfer=geo)
        B = A.astype(jnp.bfloat16)
        assert B.geo_meta == A.geo_meta
        assert B.geo_w.dtype == jnp.bfloat16
        x = np.random.default_rng(6).standard_normal(A.n_cols)
        y = np.asarray(spmv(B, jnp.asarray(x, jnp.bfloat16)), np.float32)
        y_ref = M.astype(np.float64) @ x
        np.testing.assert_allclose(
            y, y_ref, rtol=0, atol=0.05 * float(np.abs(y_ref).max())
        )


def test_store_round_trip_keeps_the_format(tmp_path):
    from amgx_tpu.solvers.base import Solver

    A = SparseMatrix.from_scipy(poisson_scipy((16, 16, 16)),
                                dtype=np.float32)
    b = poisson_rhs(A.n_rows, dtype=np.float32)
    s = _pcg_amg(A)
    res = s.solve(b)
    path = tmp_path / "geo.npz"
    s.save_setup(path)
    s2 = Solver.load_setup(path)
    levels, levels2 = s.precond.levels, s2.precond.levels
    assert s2.precond.setup_stats["coarsen_calls"] == 0
    assert s2.precond.setup_profile["geo_transfer_levels"] == (
        len(levels2) - 1
    )
    for la, lb in zip(levels[:-1], levels2[:-1]):
        assert lb.P.geo_meta == la.P.geo_meta
        assert lb.R.geo_meta == la.R.geo_meta
    res2 = s2.solve(b)
    assert int(res2.iters) == int(res.iters)
    assert np.array_equal(np.asarray(res2.x), np.asarray(res.x))


def test_serve_batch_keeps_the_format():
    """Two systems of one pattern solved as one vmapped batch: the
    cached hierarchy's transfers are structured and each x matches the
    set-up-once, resetup-per-system reference."""
    from amgx_tpu.io.poisson import jittered_poisson_family
    from amgx_tpu.serve import BatchedSolveService
    from amgx_tpu.solvers.registry import make_nested

    cfg = (
        '{"config_version": 2, "solver": {"scope": "main",'
        ' "solver": "PCG", "max_iters": 100, "tolerance": 1e-8,'
        ' "monitor_residual": 1, "convergence": "RELATIVE_INI",'
        ' "preconditioner": {"scope": "amg", "solver": "AMG",'
        ' "algorithm": "AGGREGATION", "selector": "SIZE_4",'
        ' "smoother": {"scope": "j", "solver": "BLOCK_JACOBI",'
        ' "relaxation_factor": 0.8, "monitor_residual": 0},'
        ' "presweeps": 1, "postsweeps": 1, "max_iters": 1,'
        ' "min_coarse_rows": 32, "max_levels": 10,'
        ' "structure_reuse_levels": -1,'
        ' "coarse_solver": "DENSE_LU_SOLVER", "cycle": "V",'
        ' "monitor_residual": 0}}}'
    )
    systems = jittered_poisson_family((16, 16), 2, seed=5, jitter=0.05)
    svc = BatchedSolveService(config=cfg, max_batch=4)
    results = svc.solve_many(systems)
    assert svc.metrics.get("fallback_solves") == 0
    entries = list(svc.cache._entries.values())
    assert len(entries) == 1
    amg = entries[0].solver.precond
    assert amg.setup_profile["geo_transfer_levels"] == (
        len(amg.levels) - 1
    ) > 0
    s = make_nested(create_solver(AMGConfig.from_string(cfg), "default"))
    s.setup(SparseMatrix.from_scipy(systems[0][0]))
    for (sp, b), r in zip(systems, results):
        s.resetup(SparseMatrix.from_scipy(sp))
        ref = s.solve(b)
        assert int(r.iters) == int(ref.iters)
        ref_x = np.asarray(ref.x)
        assert np.linalg.norm(np.asarray(r.x) - ref_x) <= (
            1e-12 * np.linalg.norm(ref_x)
        )


@pytest.mark.parametrize("kind", ["classical", "matching"])
def test_other_hierarchies_keep_their_formats(kind):
    A = SparseMatrix.from_scipy(poisson_scipy((12, 12, 12)),
                                dtype=np.float32)
    cfg = (
        _amg_cfg("PMIS", "CLASSICAL") if kind == "classical"
        else _amg_cfg("SIZE_8", extra=', "structured_aggregation": 0')
    )
    amg = _pcg_amg(A, cfg).precond
    assert len(amg.levels) >= 2
    assert amg.setup_profile["geo_transfer_levels"] == 0
    for lvl in amg.levels[:-1]:
        for M in (lvl.P, lvl.R):
            assert not M.has_geo_transfer and M.geo_w is None
            assert M.has_ell or M.has_dense


def test_row_sharded_hierarchy_keeps_its_ell_transfers():
    from jax.sharding import Mesh

    from amgx_tpu.distributed.amg import DistributedAMG

    Asp = poisson_scipy((24, 24, 24)).tocsr()
    mesh = Mesh(np.array(jax.devices()[:2]), ("x",))
    solver = DistributedAMG(Asp, mesh)
    sharded = [lvl for lvl in solver.h.levels if lvl.P_cols is not None]
    assert sharded
    for lvl in sharded:
        # stacked padded ELL blocks, one per shard
        assert lvl.P_cols.ndim == 3 and lvl.R_cols.ndim == 3
    b = poisson_rhs(Asp.shape[0])
    x, iters, _ = solver.solve(b, max_iters=100, tol=1e-8)
    assert np.linalg.norm(b - Asp @ x) / np.linalg.norm(b) < 1e-7
