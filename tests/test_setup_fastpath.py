"""AMG cold set-up: transfer discipline, the host CSR memo, the
vectorized row reductions and the setup-phase profiler.

The set-up keeps the whole coarsening chain host-resident and ships the
finished hierarchy in one batched device_put.  The hierarchies it builds
are checked against an independent reference in
tests/test_classical_reference.py and tests/test_amg.py.
"""

import os

import numpy as np

import amgx_tpu.amg  # noqa: F401 — registers the "AMG" solver
from amgx_tpu.config.amg_config import AMGConfig
from amgx_tpu.core import profiling
from amgx_tpu.io.poisson import (
    poisson_2d_5pt,
    poisson_3d_7pt,
    poisson_rhs,
)
from amgx_tpu.solvers import create_solver

CLASSICAL = """
{"config_version": 2,
 "solver": {"scope": "main", "solver": "PCG", "max_iters": 100,
    "tolerance": 1e-8, "monitor_residual": 1,
    "convergence": "RELATIVE_INI",
    "preconditioner": {"scope": "amg", "solver": "AMG",
       "algorithm": "CLASSICAL", "selector": "PMIS",
       "smoother": {"scope": "jac", "solver": "BLOCK_JACOBI",
           "relaxation_factor": 0.8, "monitor_residual": 0},
       "presweeps": 1, "postsweeps": 1, "max_levels": 20,
       "min_coarse_rows": 16, "coarse_solver": "DENSE_LU_SOLVER",
       "cycle": "V", "max_iters": 1, "monitor_residual": 0}}}
"""


def test_fastpath_single_transfer_batch():
    """Transfer-count regression: a cold setup ships the whole
    hierarchy in at most ONE host->device transfer batch."""
    A = poisson_2d_5pt(48)

    before = profiling.setup_transfer_count[0]
    s = create_solver(AMGConfig.from_string(CLASSICAL), "default")
    s.setup(A)
    batches = profiling.setup_transfer_count[0] - before
    assert batches <= 1, batches
    # and the batch actually carried the hierarchy
    prof = s.collect_setup_profile()
    assert prof.get("transfer_batches", 0) == batches
    assert prof.get("transfer_arrays", 0) > 0


def test_fastpath_block_matrix_single_batch():
    """Block systems keep the ≤1-transfer-batch invariant: the scalar
    expansion rides the batched finalize instead of uploading eagerly
    mid-setup, and the set-up hierarchy still solves."""
    import warnings

    from amgx_tpu.core.matrix import SparseMatrix

    sp = poisson_2d_5pt(24).to_scipy().tocsr()
    A = SparseMatrix.from_scipy(sp, block_size=2)
    b = poisson_rhs(sp.shape[0])

    before = profiling.setup_transfer_count[0]
    s = create_solver(AMGConfig.from_string(CLASSICAL), "default")
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # scalar-expansion notice
        s.setup(A)
    assert profiling.setup_transfer_count[0] - before <= 1
    assert bool(s.solve(b).converged)


def test_host_csr_device_consistency():
    """The lazy host memo reads the matrix's own (immutable) device
    buffers, so host_csr() can never desynchronize from the values
    the solve uses — even if the caller mutates the upload arrays
    afterwards (on CPU, jax may alias them zero-copy; on
    accelerators, the upload is a snapshot — either way host view ==
    device values)."""
    from amgx_tpu.core.matrix import SparseMatrix

    sp = poisson_2d_5pt(8).to_scipy().tocsr()
    data = sp.data.copy()
    A = SparseMatrix.from_csr(sp.indptr, sp.indices, data,
                              n_cols=sp.shape[1])
    data *= 1e6  # caller mutates their buffer post-upload
    assert np.array_equal(A.host_csr().data, np.asarray(A.values))
    # and the triple is memoized (materialized at most once)
    A.host_csr()
    c1 = A._host_csr_cache
    A.host_csr()
    assert A._host_csr_cache is c1


def test_setup_profile_phases():
    """The setup profiler records the phase anatomy on the AMG solver
    and PCG's collect_setup_profile surfaces it (the obtain_timings
    ``setup:<phase>`` source)."""
    A = poisson_2d_5pt(32)
    s = create_solver(AMGConfig.from_string(CLASSICAL), "default")
    s.setup(A)
    amg_prof = s.precond.setup_profile
    for phase in ("strength", "cf_split", "interp", "rap_execute",
                  "transfer", "finalize"):
        assert phase in amg_prof, (phase, sorted(amg_prof))
        assert amg_prof[phase] >= 0.0
    # merged through the Krylov wrapper
    merged = s.collect_setup_profile()
    assert merged["strength"] == amg_prof["strength"]


def test_setup_profile_env_dump(capsys):
    """AMGX_TPU_SETUP_PROFILE=1 dumps the phase table at setup."""
    os.environ["AMGX_TPU_SETUP_PROFILE"] = "1"
    try:
        s = create_solver(AMGConfig.from_string(CLASSICAL), "default")
        s.setup(poisson_2d_5pt(24))
    finally:
        os.environ.pop("AMGX_TPU_SETUP_PROFILE", None)
    out = capsys.readouterr().out
    assert "AMG setup profile" in out
    assert "setup:strength" in out


def test_row_reductions_bitwise():
    """The vectorized row reductions are bitwise-identical to the
    ufunc.at forms on adversarial data (empty rows, f32 values into
    f64 accumulators, negative maxima)."""
    from amgx_tpu.amg.classical import _row_max, _row_sum

    # the exact shape that broke the clamped-reduceat variant: a
    # trailing empty row truncating the last non-empty row's segment
    got = _row_max(
        np.array([1.0, 2.0, 3.0, 4.0, 9.0]),
        np.array([0, 2, 5, 5]),
        np.array([0, 0, 1, 1, 1]),
        0.0,
    )
    assert np.array_equal(got, [2.0, 9.0, 0.0]), got

    rng = np.random.default_rng(7)
    n = 257
    lens = rng.integers(0, 31, n)  # empty rows included
    lens[-3:] = 0  # trailing empty rows (the reduceat edge case)
    lens[0] = 0  # leading empty row
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(lens, out=indptr[1:])
    nnz = int(indptr[-1])
    row_ids = np.repeat(np.arange(n), lens)
    for dtype in (np.float64, np.float32):
        vals = rng.standard_normal(nnz).astype(dtype) * 1e3

        ref_sum = np.zeros(n)
        np.add.at(ref_sum, row_ids, vals)
        ref_max = np.full(n, 0.0)
        np.maximum.at(ref_max, row_ids, vals)
        fast_sum = _row_sum(row_ids, vals, n)
        fast_max = _row_max(vals, indptr, row_ids, 0.0,
                            out_dtype=np.float64)

        assert np.array_equal(fast_sum, ref_sum)
        assert np.array_equal(fast_max, ref_max)


def test_fastpath_resetup_structure_reuse():
    """Deferred-then-uploaded Galerkin plans drive the values-only
    resetup."""
    cfg_s = CLASSICAL.replace(
        '"min_coarse_rows": 16',
        '"min_coarse_rows": 16, "structure_reuse_levels": -1',
    )
    A = poisson_2d_5pt(32)
    b = poisson_rhs(A.n_rows)
    s = create_solver(AMGConfig.from_string(cfg_s), "default")
    s.setup(A)
    assert s.precond.levels[0].rap_plan is not None
    res1 = s.solve(b)
    sp = A.to_scipy()
    sp.data = sp.data * 1.5
    from amgx_tpu.core.matrix import SparseMatrix

    A2 = SparseMatrix.from_scipy(sp)
    s.resetup(A2)
    assert s.precond.setup_stats["coarsen_calls"] == 1  # no re-coarsen
    res2 = s.solve(b)
    assert bool(res2.converged)
    # scaled operator, same spectrum shape: solution is x1 / 1.5
    np.testing.assert_allclose(
        np.asarray(res2.x) * 1.5, np.asarray(res1.x), rtol=1e-6
    )


def test_device_setup_per_call_profile():
    """device_setup profiling state is per-call: two builds get their
    own host/device splits (the old module-global accumulators were
    corruptible by concurrent setups)."""
    import scipy.sparse as sps

    from amgx_tpu.amg.device_setup import build_classical_level_device
    from amgx_tpu.core.profiling import setup_profile_scope

    cfg = AMGConfig.from_string(
        '{"config_version": 2, "solver": {"scope": "main", '
        '"solver": "AMG", "algorithm": "CLASSICAL", '
        '"selector": "PMIS", "interpolator": "D1"}}'
    )
    Asp = poisson_2d_5pt(12).to_scipy().tocsr()
    p1: dict = {}
    p2: dict = {}
    for p in (p1, p2):
        with setup_profile_scope(p):
            build_classical_level_device(Asp, cfg, "main", 0)
    for p in (p1, p2):
        assert p["syncs"] > 0
        assert p["host_s"] > 0.0 and p["device_s"] > 0.0
        assert p["transfer_s"] > 0.0
    # independent accumulation, not a shared running total
    assert p1["syncs"] == p2["syncs"]


def test_host_csr_no_download():
    """host_csr() serves the construction-time memo (no device
    download) and matches to_scipy bit for bit."""
    A = poisson_3d_7pt(8)
    sp_host = A.host_csr()
    sp_copy = A.to_scipy()
    assert (sp_host != sp_copy).nnz == 0
    assert np.array_equal(sp_host.data, sp_copy.data)
    # values-only rebuilds must drop the memo (values changed)
    A2 = A.replace_values(np.asarray(A.values) * 2.0)
    assert getattr(A2, "_host_csr_cache", None) is None
