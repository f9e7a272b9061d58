"""AMG hierarchy tests (reference src/tests/: nested_amg_equivalence.cu,
aggregates_coarsening_factor.cu, classical_pmis.cu,
fgmres_convergence_poisson.cu)."""

import numpy as np
import os

import pytest

import amgx_tpu
from amgx_tpu.config.amg_config import AMGConfig
from amgx_tpu.io.poisson import poisson_2d_5pt, poisson_3d_7pt, poisson_rhs
from amgx_tpu.solvers import create_solver
from amgx_tpu.solvers.base import SUCCESS

amgx_tpu.initialize()


def _solve(cfg_text, A, b):
    cfg = AMGConfig.from_string(cfg_text)
    s = create_solver(cfg, "default")
    s.setup(A)
    return s, s.solve(b)


AMG_STANDALONE = """
{"config_version": 2,
 "solver": {"scope": "main", "solver": "AMG", "algorithm": "%s",
    "selector": "%s", "smoother": {"scope": "jac", "solver": "BLOCK_JACOBI",
        "relaxation_factor": 0.8, "monitor_residual": 0},
    "presweeps": 2, "postsweeps": 2, "max_levels": 20,
    "min_coarse_rows": 16, "coarse_solver": "DENSE_LU_SOLVER",
    "cycle": "%s", "max_iters": 60, "monitor_residual": 1,
    "convergence": "RELATIVE_INI", "tolerance": 1e-08, "norm": "L2"}}
"""


@pytest.mark.parametrize("cycle", ["V", "W", "F"])
def test_aggregation_amg_poisson2d(cycle):
    A = poisson_2d_5pt(32)
    b = poisson_rhs(A.n_rows)
    s, res = _solve(AMG_STANDALONE % ("AGGREGATION", "SIZE_2", cycle), A, b)
    assert int(res.status) == SUCCESS
    x = np.asarray(res.x)
    rel = np.linalg.norm(b - A.to_scipy() @ x) / np.linalg.norm(b)
    assert rel < 1e-7
    # unsmoothed aggregation V-cycle converges slowly (rate ~0.7, the
    # reference pairs it with Krylov); W/F accelerate it
    limit = {"V": 60, "W": 30, "F": 35}[cycle]
    assert int(res.iters) < limit
    # hierarchy actually coarsened
    assert len(s.levels) >= 3
    assert s.levels[1].n_rows < s.levels[0].n_rows


def test_classical_amg_poisson2d():
    A = poisson_2d_5pt(32)
    b = poisson_rhs(A.n_rows)
    s, res = _solve(AMG_STANDALONE % ("CLASSICAL", "PMIS", "V"), A, b)
    assert int(res.status) == SUCCESS
    # PMIS+D1 rate; D2 interpolation will tighten this
    assert int(res.iters) < 45
    assert len(s.levels) >= 2


def test_amg_convergence_rate_scales():
    """Multigrid signature: W-cycle iteration count roughly constant as n
    grows (unsmoothed-aggregation V-cycles degrade with n — the known
    theory — so the scalability check uses W)."""
    iters = []
    for nx in (16, 32):
        A = poisson_2d_5pt(nx)
        b = poisson_rhs(A.n_rows)
        s, res = _solve(AMG_STANDALONE % ("AGGREGATION", "SIZE_2", "W"),
                        A, b)
        iters.append(int(res.iters))
    assert iters[1] <= iters[0] + 6


def test_pcg_amg_preconditioner():
    A = poisson_3d_7pt(12)
    b = poisson_rhs(A.n_rows)
    cfg_text = """
    {"config_version": 2,
     "solver": {"scope": "main", "solver": "PCG", "max_iters": 100,
        "monitor_residual": 1, "convergence": "RELATIVE_INI",
        "tolerance": 1e-08, "norm": "L2",
        "preconditioner": {"scope": "amg", "solver": "AMG",
            "algorithm": "AGGREGATION", "selector": "SIZE_2",
            "smoother": {"scope": "jac", "solver": "BLOCK_JACOBI",
                         "relaxation_factor": 0.8, "monitor_residual": 0},
            "presweeps": 1, "postsweeps": 1, "max_iters": 1,
            "min_coarse_rows": 16, "max_levels": 20,
            "coarse_solver": "DENSE_LU_SOLVER", "cycle": "V",
            "monitor_residual": 0}}}
    """
    s, res = _solve(cfg_text, A, b)
    assert int(res.status) == SUCCESS
    assert int(res.iters) < 25  # AMG-PCG converges fast


@pytest.mark.skipif(
    not os.path.isdir("/root/reference"),
    reason="reference AmgX tree not mounted in this environment",
)
def test_fgmres_aggregation_reference_config():
    """The FGMRES_AGGREGATION.json shipped config (BASELINE acceptance
    config 1) — adapted: DILU smoother, SIZE_2, V-cycle."""
    from amgx_tpu.io.matrix_market import read_mtx

    A = read_mtx("/root/reference/examples/matrix.mtx")
    b = np.ones(A.n_rows)
    cfg = AMGConfig.from_file(
        "/root/reference/src/configs/FGMRES_AGGREGATION.json"
    )
    s = create_solver(cfg, "default")
    s.setup(A)
    res = s.solve(b)
    assert int(res.status) == SUCCESS
    x = np.asarray(res.x)
    rel = np.linalg.norm(b - A.to_scipy() @ x) / np.linalg.norm(b)
    assert rel < 1e-6
    # reference README shows 1 iteration on this 12x12 system
    assert int(res.iters) <= 3


def test_grid_stats_output(capsys):
    A = poisson_2d_5pt(24)
    cfg_text = AMG_STANDALONE % ("AGGREGATION", "SIZE_2", "V")
    cfg_text = cfg_text.replace('"solver": "AMG"',
                                '"solver": "AMG", "print_grid_stats": 1')
    cfg = AMGConfig.from_string(cfg_text)
    s = create_solver(cfg, "default")
    s.setup(A)
    out = capsys.readouterr().out
    assert "Number of Levels" in out
    assert "Grid Complexity" in out


def test_aggregation_coarsening_factor():
    """SIZE_2 halves; SIZE_4 quarters (reference
    aggregates_coarsening_factor.cu)."""
    from amgx_tpu.amg.aggregation import aggregate

    A = poisson_2d_5pt(24).to_scipy()
    for passes, lo, hi in [(1, 1.7, 2.4), (2, 3.0, 6.0)]:
        agg = aggregate(A, passes)
        ratio = A.shape[0] / (int(agg.max()) + 1)
        assert lo < ratio < hi, (passes, ratio)


def test_d2_interpolation_h_independent():
    """Standard (D2) interpolation gives near-h-independent V-cycle
    convergence on Poisson where D1 degrades (the reason the shipped
    classical configs default to D2)."""
    tpl = AMG_STANDALONE % ("CLASSICAL", "PMIS", "V")
    tpl = tpl.replace('"selector": "PMIS"',
                      '"selector": "PMIS", "interpolator": "D2"')
    iters = []
    for nx in (16, 48):
        A = poisson_2d_5pt(nx)
        b = poisson_rhs(A.n_rows)
        s, res = _solve(tpl, A, b)
        assert int(res.status) == SUCCESS
        iters.append(int(res.iters))
    assert iters[1] <= iters[0] + 8, iters


def test_d2_interp_rows_sum_to_one():
    """For a zero-row-sum operator, interpolation rows over F points sum
    to ~1 (constant preservation)."""
    from amgx_tpu.amg.classical import (
        pmis_select,
        standard_interpolation,
        strength_ahat,
    )
    import scipy.sparse as sps

    A = poisson_2d_5pt(20).to_scipy().tolil()
    A.setdiag(0.0)
    A.setdiag(-np.asarray(A.sum(axis=1)).ravel())  # zero row sums
    A = A.tocsr()
    S = strength_ahat(A, 0.25, 1.1)
    cf = pmis_select(S)
    P = standard_interpolation(A, S, cf)
    rs = np.asarray(P.sum(axis=1)).ravel()
    interior = np.abs(np.asarray(A.sum(axis=1)).ravel()) < 1e-12
    np.testing.assert_allclose(rs[interior], 1.0, rtol=1e-10)


def test_pmis_valid_splitting():
    from amgx_tpu.amg.classical import pmis_select, strength_ahat

    A = poisson_2d_5pt(20).to_scipy()
    S = strength_ahat(A, 0.25, 1.1)
    cf = pmis_select(S)
    assert cf.sum() > 0
    # every F point has at least one strong C neighbour (distance-1 cover)
    import scipy.sparse as sps

    Ssym = ((S + S.T) > 0).astype(np.int8)
    cover = Ssym @ cf
    fine = cf == 0
    assert np.all(cover[fine] > 0)


def test_interp_truncation():
    from amgx_tpu.amg.classical import truncate_interp
    import scipy.sparse as sps

    P = sps.csr_matrix(
        np.array([[0.5, 0.3, 0.01], [1.0, 0.0, 0.0], [0.2, 0.2, 0.2]])
    )
    Pt = truncate_interp(P, 0.1, -1)
    assert Pt.nnz < P.nnz
    # row sums preserved
    np.testing.assert_allclose(
        np.asarray(Pt.sum(axis=1)).ravel(),
        np.asarray(P.sum(axis=1)).ravel(),
        rtol=1e-12,
    )
    Pk = truncate_interp(P, 1.1, 2)
    assert np.all(np.diff(Pk.indptr) <= 2)


def test_energymin_amg():
    """ENERGYMIN algorithm (reference src/energymin)."""
    A = poisson_2d_5pt(24)
    b = poisson_rhs(A.n_rows)
    s, res = _solve(AMG_STANDALONE % ("ENERGYMIN", "PMIS", "V"), A, b)
    assert int(res.status) == SUCCESS
    assert int(res.iters) < 30
    assert len(s.levels) >= 2


def test_energymin_reduces_energy_heterogeneous():
    """EM interpolation strictly reduces trace(P^T A P) vs D1 on
    heterogeneous operators while preserving row sums (on symmetric
    grids D1 is already stationary)."""
    import scipy.sparse as sps
    from amgx_tpu.amg.classical import (
        direct_interpolation, pmis_select, strength_ahat,
    )
    from amgx_tpu.amg.energymin import energymin_interpolation

    A = poisson_2d_5pt(24).to_scipy()
    rng = np.random.default_rng(1)
    w = 10.0 ** rng.uniform(-1, 1, A.shape[0])
    Ah = (sps.diags_array(np.sqrt(w)) @ A @ sps.diags_array(np.sqrt(w))
          ).tocsr()
    S = strength_ahat(Ah, 0.25, 1.1)
    cf = pmis_select(S)
    P1 = direct_interpolation(Ah, S, cf)
    P2 = energymin_interpolation(Ah, S, cf)
    e1 = (P1.T @ Ah @ P1).diagonal().sum()
    e2 = (P2.T @ Ah @ P2).diagonal().sum()
    assert e2 < e1
    drift = np.abs(np.asarray((P2 - P1).sum(axis=1))).max()
    assert drift < 1e-10


def test_affinity_strength_amg():
    """AFFINITY strength (reference classical_strength_affinity.cu):
    correlation of relaxed test vectors; the resulting AMG must solve
    Poisson, and on an anisotropic operator affinity must find the
    strong (stiff) direction."""
    import scipy.sparse as sps
    from amgx_tpu.amg.classical import strength_affinity

    tpl = AMG_STANDALONE % ("CLASSICAL", "PMIS", "V")
    tpl = tpl.replace('"selector": "PMIS"',
                      '"selector": "PMIS", "strength": "AFFINITY"')
    A = poisson_2d_5pt(24)
    b = poisson_rhs(A.n_rows)
    s, res = _solve(tpl, A, b)
    assert int(res.status) == SUCCESS

    # anisotropic: strong couplings must align with the stiff axis
    n = 16
    T = sps.diags_array([-np.ones(n - 1), 2 * np.ones(n),
                         -np.ones(n - 1)], offsets=[-1, 0, 1])
    I = sps.eye_array(n)
    Ah = (sps.kron(I, T) + 100.0 * sps.kron(T, I)).tocsr()
    S = strength_affinity(Ah, 0.5)
    coo = S.tocoo()
    stiff = np.abs(coo.col - coo.row) >= n  # y-direction couplings
    assert stiff.mean() > 0.8  # strong links predominantly stiff-axis


# ---------------------------------------------------------------------------
# structured (geometric) aggregation — the TPU all-DIA hierarchy path
# (reference GEO selector, src/aggregation/selectors/geo_selector.cu; here
# geometry is inferred from the stencil diagonals)


def test_infer_grid_from_stencils():
    from amgx_tpu.amg.aggregation import infer_grid, stencil_offsets

    A3 = poisson_3d_7pt(12).to_scipy()
    assert infer_grid(stencil_offsets(A3), 12 ** 3) == (12, 12, 12)
    A2 = poisson_2d_5pt(20).to_scipy()
    nx, ny, nz = infer_grid(stencil_offsets(A2), 400)
    assert (nx, ny) == (20, 20) and nz == 1
    # unstructured matrix -> None
    from tests.conftest import random_csr

    R = random_csr(512, density=0.02, seed=5)
    offs = stencil_offsets(R)
    assert offs is None or infer_grid(offs, 512) is None


def test_geo_aggregate_blocks():
    from amgx_tpu.amg.aggregation import geo_aggregate

    agg = geo_aggregate(4, 4, 4, 3)  # 2x2x2 blocks
    assert agg.shape == (64,)
    assert int(agg.max()) + 1 == 8
    sizes = np.bincount(agg)
    assert (sizes == 8).all()
    # lexicographic block numbering: node (0,0,0) and (1,1,1) share a block
    assert agg[0] == agg[1 + 4 + 16]


def test_structured_aggregation_all_dia_hierarchy():
    """Every Galerkin coarse operator of a stencil problem stays DIA."""
    A = poisson_3d_7pt(16)
    b = poisson_rhs(A.n_rows)
    s, res = _solve(
        AMG_STANDALONE % ("AGGREGATION", "SIZE_8", "V"), A, b
    )
    assert int(res.status) == SUCCESS
    for lvl in s.levels:
        assert lvl.A.has_dia or lvl.A.n_rows <= 64, (
            lvl.level_id,
            lvl.A.n_rows,
        )


def test_structured_aggregation_opt_out():
    from amgx_tpu.amg.aggregation import build_aggregation_level

    A = poisson_3d_7pt(8).to_scipy()
    cfg = AMGConfig.from_string(
        '{"config_version": 2, "solver": {"scope": "main",'
        ' "solver": "AMG", "selector": "SIZE_2",'
        ' "structured_aggregation": 0}}'
    )
    P, R, Ac, _ = build_aggregation_level(A, cfg, "main")
    # matching-based path still works and coarsens
    assert Ac.shape[0] < A.shape[0]


def test_geo_aggregation_semicoarsens_anisotropic():
    """Strong-axis aggregation on anisotropic stencils (the geometric
    analogue of strength-of-connection: weak couplings must not be
    aggregated across)."""
    from amgx_tpu.amg.aggregation import (
        axis_strengths,
        geo_aggregate,
        infer_grid,
        stencil_offsets,
    )
    import scipy.sparse as sps

    # 2D anisotropic diffusion: -u_xx - eps*u_yy, eps=1e-3
    nx = ny = 16
    eps = 1e-3
    n = nx * ny
    main = np.full(n, 2.0 + 2.0 * eps)
    ex = np.full(n - 1, -1.0)
    ex[nx - 1 :: nx] = 0.0
    ey = np.full(n - nx, -eps)
    A = sps.diags_array(
        [main, ex, ex, ey, ey], offsets=[0, 1, -1, nx, -nx]
    ).tocsr()
    grid = infer_grid(stencil_offsets(A), n)
    assert grid == (nx, ny, 1)
    s = axis_strengths(A, *grid)
    assert s[0] > 100 * s[1]
    agg = geo_aggregate(*grid, 3, strengths=s)
    # 8x1 blocks along x: node (0,0) through (7,0) share an aggregate,
    # nodes differing in y do not
    assert agg[0] == agg[7]
    assert agg[0] != agg[nx]


def test_profiling_hooks():
    """Named HLO scopes per cycle phase and level (reference
    amgx_timer.h:32-60 nvtxRange/levelProfile; SURVEY §5.1)."""
    import jax

    from amgx_tpu.core.profiling import trace_range

    A = poisson_3d_7pt(8)
    b = poisson_rhs(A.n_rows)
    cfg = AMGConfig.from_string(
        AMG_STANDALONE % ("AGGREGATION", "SIZE_2", "V")
    )
    s = create_solver(cfg, "default")
    s.setup(A)
    # the traced cycle carries named scopes into the HLO metadata
    cyc = s.make_cycle()
    params = s.apply_params()
    import jax.numpy as jnp

    lowered = jax.jit(cyc).lower(
        params, jnp.asarray(b), jnp.zeros_like(jnp.asarray(b))
    )
    try:
        hlo = lowered.as_text(debug_info=True)
    except TypeError:
        # older jax: Lowered.as_text() has no debug_info and strips
        # scope metadata — the COMPILED module keeps op_name metadata
        hlo = lowered.compile().as_text()
    assert "amg_l0_restrict" in hlo
    assert "amg_coarse_solve" in hlo
    # API-level trace spans are usable as context managers
    with trace_range("AMGX_test_span"):
        pass


def test_geo_galerkin_dense_reduction_matches_sparse_product():
    """geo_galerkin_dia (the no-intermediate Galerkin for geometric
    aggregations, replacing the reference's SpGEMM hash kernels at
    scale) == R A P exactly, in 3D and 2D and with semicoarsening."""
    import scipy.sparse as sps

    from amgx_tpu.amg.aggregation import (
        geo_galerkin_dia,
        select_aggregates,
    )

    cfg = AMGConfig.from_string(
        '{"config_version": 2, "solver": {"scope": "m",'
        ' "solver": "AMG", "selector": "SIZE_8"}}'
    )
    cases = [poisson_3d_7pt(12).to_scipy(), poisson_2d_5pt(16).to_scipy()]
    # anisotropic: semicoarsening picks non-cubic blocks
    n2 = 16 * 16
    main = np.full(n2, 2.0 + 2.0e-3)
    ex = np.full(n2 - 1, -1.0)
    ex[15::16] = 0.0
    ey = np.full(n2 - 16, -1e-3)
    cases.append(
        sps.diags_array(
            [main, ex, ex, ey, ey], offsets=[0, 1, -1, 16, -16]
        ).tocsr()
    )
    ran = 0
    for Asp in cases:
        agg, geo = select_aggregates(Asp, cfg, "m")
        assert geo is not None
        Ac = geo_galerkin_dia(Asp, *geo)
        if Ac is None:
            continue  # ragged blocks: sparse fallback covers it
        ran += 1
        n = Asp.shape[0]
        nc = int(agg.max()) + 1
        P = sps.csr_matrix(
            (np.ones(n), (np.arange(n), agg)), shape=(n, nc)
        )
        ref = (P.T @ Asp @ P).tocsr()
        assert abs(Ac - ref).max() < 1e-12
    assert ran >= 2, ran


def test_aggregation_hierarchy_galerkin_matches_scipy():
    """Every coarse operator of a SIZE_4 PCG-AMG hierarchy on 12^3
    float32 equals scipy's R A P of the downloaded level, to float32
    rounding (a few ulps of the largest entry)."""
    cfg = AMGConfig.from_string(
        '{"config_version": 2, "solver": {"scope": "main",'
        ' "solver": "PCG", "max_iters": 100, "tolerance": 1e-6,'
        ' "convergence": "RELATIVE_INI",'
        ' "preconditioner": {"scope": "amg", "solver": "AMG",'
        ' "algorithm": "AGGREGATION", "selector": "SIZE_4",'
        ' "smoother": {"scope": "jac", "solver": "BLOCK_JACOBI",'
        ' "relaxation_factor": 0.8, "monitor_residual": 0},'
        ' "presweeps": 1, "postsweeps": 1, "max_levels": 20,'
        ' "min_coarse_rows": 64, "coarse_solver": "DENSE_LU_SOLVER",'
        ' "cycle": "V", "max_iters": 1, "monitor_residual": 0}}}'
    )
    A = poisson_3d_7pt(12, dtype=np.float32)
    s = create_solver(cfg, "default")
    s.setup(A)
    assert bool(s.solve(poisson_rhs(A.n_rows, dtype=np.float32)).converged)
    levels = s.precond.levels
    assert len(levels) >= 3
    tol = 8 * np.finfo(np.float32).eps
    for fine, coarse in zip(levels[:-1], levels[1:]):
        Af, P, R = (
            M.to_scipy().astype(np.float64)
            for M in (fine.A, fine.P, fine.R)
        )
        ref = (R @ Af @ P).tocsr()
        Ac = coarse.A.to_scipy()
        assert Ac.dtype == np.float32
        assert Ac.shape == ref.shape
        assert abs(Ac.astype(np.float64) - ref).max() <= (
            tol * abs(ref).max()
        )


def test_geo_galerkin_rejects_wrap_and_ambiguity():
    """Periodic (wrap) diagonals and thin grids with ambiguous offset
    decompositions must fall back to the sparse product, never build a
    wrong coarse operator silently."""
    import scipy.sparse as sps

    from amgx_tpu.amg.aggregation import (
        _decompose_offset,
        geo_galerkin_dia,
    )

    # x-periodic 2D Poisson: wrap offset +-(nx-1) carries nonzeros at
    # out-of-window rows
    nx = 8
    n = nx * nx
    main = np.full(n, 4.0)
    ex = np.full(n - 1, -1.0)
    ex[nx - 1 :: nx] = 0.0
    ey = np.full(n - nx, -1.0)
    wrap = np.zeros(n - (nx - 1))
    wrap[::nx] = -1.0  # couples (0,y) <-> (nx-1,y)
    A = sps.diags_array(
        [main, ex, ex, ey, ey, wrap, wrap],
        offsets=[0, 1, -1, nx, -nx, nx - 1, -(nx - 1)],
    ).tocsr()
    assert geo_galerkin_dia(A, (nx, nx, 1), (2, 2, 1)) is None

    # thin grid: offset +1 on a (2,2,N) grid is ambiguous within reach 2
    assert _decompose_offset(1, 2, 2, 100, 2) is None


def test_geo_rap_dispatch_above_threshold(monkeypatch):
    """build_aggregation_level routes through the dense-reduction
    Galerkin above _GEO_RAP_MIN_ROWS and the hierarchy it feeds stays
    correct."""
    import amgx_tpu.amg.aggregation as agg

    monkeypatch.setattr(agg, "_GEO_RAP_MIN_ROWS", 1000)
    calls = []
    real = agg.geo_galerkin_dia

    def spy(Asp, grid, block):
        out = real(Asp, grid, block)
        calls.append((Asp.shape[0], out is not None))
        return out

    monkeypatch.setattr(agg, "geo_galerkin_dia", spy)
    A = poisson_3d_7pt(16)
    b = poisson_rhs(A.n_rows)
    s, res = _solve(
        AMG_STANDALONE % ("AGGREGATION", "SIZE_8", "V"), A, b
    )
    assert int(res.status) == SUCCESS
    # fine level (4096 rows) went through the geo product
    assert any(n >= 1000 and ok for n, ok in calls), calls


def test_device_matcher_bit_identical_to_host():
    """The on-device handshake matcher (VERDICT r3 #6: setup matching
    moved off host) produces bit-identical aggregates to the host
    numpy rounds — same selection keys (strongest weight, jitter
    tie-break), so golden iteration counts cannot shift."""
    import numpy as np
    import scipy.sparse as sps

    from amgx_tpu.amg.aggregation import (
        edge_weights,
        pairwise_match,
        pairwise_match_device,
    )
    from amgx_tpu.io.poisson import poisson_3d_7pt

    for A in (
        poisson_3d_7pt(16).to_scipy().tocsr(),
        (lambda G: ((G + G.T) != 0).astype(float).tocsr())(
            sps.random(
                3000, 3000, density=0.002,
                random_state=np.random.default_rng(5),
            )
        ),
    ):
        W = edge_weights(A, 0)
        h = pairwise_match(W)
        d = pairwise_match_device(W)
        assert np.array_equal(h, d)
