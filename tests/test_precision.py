"""Cheap-preconditioner tests: per-level mixed-precision hierarchies,
inexact coarse solves, the f64 refinement accuracy envelope, and the
mixed-dtype store/serve/telemetry surfaces (PR 13)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sps

import amgx_tpu

amgx_tpu.initialize()

from amgx_tpu.config.amg_config import AMGConfig
from amgx_tpu.core.matrix import SparseMatrix
from amgx_tpu.io.poisson import poisson_scipy
from amgx_tpu.solvers.base import Solver
from amgx_tpu.solvers.registry import create_solver, make_nested


def _poisson(shape=(24, 24), seed=0):
    sp = poisson_scipy(shape).tocsr()
    sp.sort_indices()
    rng = np.random.default_rng(seed)
    return sp, rng.standard_normal(sp.shape[0])


def _amg_cfg(coarse="DENSE_LU_SOLVER", extra_amg="", outer_tol=1e-10,
             max_levels=10):
    return (
        '{"config_version": 2, "solver": {"scope": "main",'
        ' "solver": "PCG", "max_iters": 200,'
        f' "tolerance": {outer_tol}, "monitor_residual": 1,'
        ' "convergence": "RELATIVE_INI",'
        ' "preconditioner": {"scope": "amg", "solver": "AMG",'
        ' "algorithm": "AGGREGATION", "selector": "SIZE_8",'
        + extra_amg +
        ' "smoother": {"scope": "sm", "solver": "OPT_POLYNOMIAL",'
        ' "chebyshev_polynomial_order": 2, "monitor_residual": 0},'
        ' "presweeps": 1, "postsweeps": 1, "max_iters": 1,'
        f' "min_coarse_rows": 32, "max_levels": {max_levels},'
        ' "structure_reuse_levels": -1,'
        f' "coarse_solver": "{coarse}", "cycle": "V",'
        ' "monitor_residual": 0}}}'
    )


def _refine_cfg(hier_dtype="FLOAT32", policy="ALL",
                coarse="INEXACT", extra_outer=""):
    return (
        '{"config_version": 2, "solver": {"scope": "main",'
        ' "solver": "ITERATIVE_REFINEMENT", "max_iters": 60,'
        ' "tolerance": 1e-8, "monitor_residual": 1,'
        f' "convergence": "RELATIVE_INI", {extra_outer}'
        ' "preconditioner": {"scope": "inner", "solver": "PCG",'
        ' "max_iters": 8, "monitor_residual": 0,'
        ' "preconditioner": {"scope": "amg", "solver": "AMG",'
        ' "algorithm": "AGGREGATION", "selector": "SIZE_8",'
        f' "hierarchy_dtype": "{hier_dtype}",'
        f' "level_dtype_policy": "{policy}",'
        ' "smoother": {"scope": "sm", "solver": "OPT_POLYNOMIAL",'
        ' "chebyshev_polynomial_order": 2, "monitor_residual": 0},'
        ' "presweeps": 1, "postsweeps": 1, "max_iters": 1,'
        ' "min_coarse_rows": 32, "max_levels": 10,'
        ' "structure_reuse_levels": -1,'
        f' "coarse_solver": "{coarse}", "cycle": "V",'
        ' "monitor_residual": 0}}}}'
    )


def _solver(cfg_text):
    s = make_nested(
        create_solver(AMGConfig.from_string(cfg_text), "default")
    )
    return s


def _amg_of(s):
    """The AMG instance inside a PCG or refinement-wrapped solver."""
    inner = getattr(s, "inner", None)
    if inner is not None:  # ITERATIVE_REFINEMENT -> PCG -> AMG
        return inner.precond
    return s.precond


# ---------------------------------------------------------------------
# per-level precision policy


def test_hierarchy_dtype_policy_coarse():
    sp, b = _poisson()
    s = _solver(_amg_cfg(extra_amg='"hierarchy_dtype": "FLOAT32",'))
    s.setup(SparseMatrix.from_scipy(sp))
    amg = s.precond
    assert np.dtype(amg.levels[0].A.values.dtype) == np.float64
    for lvl in amg.levels[1:]:
        assert np.dtype(lvl.A.values.dtype) == np.float32
    for lvl in amg.levels[:-1]:
        assert np.dtype(lvl.P.values.dtype) == np.float32
        assert np.dtype(lvl.R.values.dtype) == np.float32
    res = s.solve(b)
    assert int(res.status) == 0


def test_hierarchy_dtype_policy_all():
    sp, b = _poisson()
    s = _solver(_amg_cfg(
        extra_amg='"hierarchy_dtype": "F32", "level_dtype_policy": "ALL",'
    ))
    s.setup(SparseMatrix.from_scipy(sp))
    for lvl in s.precond.levels:
        assert np.dtype(lvl.A.values.dtype) == np.float32
    res = s.solve(b)
    assert int(res.status) == 0
    # the OUTER PCG still monitors in f64, so the final tolerance is
    # the f64 one
    x = np.asarray(res.x)
    rel = np.linalg.norm(b - sp @ x) / np.linalg.norm(b)
    assert rel < 1e-8


def test_mixed_precision_iteration_parity():
    """The +10% retired-iteration envelope of the f32 hierarchy vs the
    f64 baseline at unchanged final tolerance (the precision_bench
    gate, in miniature)."""
    sp, b = _poisson()
    A = SparseMatrix.from_scipy(sp)
    base = _solver(_amg_cfg())
    base.setup(A)
    r0 = base.solve(b)
    cheap = _solver(_amg_cfg(
        extra_amg='"hierarchy_dtype": "F32", "level_dtype_policy": "ALL",'
    ))
    cheap.setup(A)
    r1 = cheap.solve(b)
    assert int(r0.status) == 0 and int(r1.status) == 0
    assert int(r1.iters) <= int(np.ceil(1.1 * int(r0.iters)))


def test_smoother_state_matches_level_dtype():
    sp, _ = _poisson((12, 12))
    s = _solver(_amg_cfg(
        extra_amg='"hierarchy_dtype": "F32", "level_dtype_policy": "ALL",'
    ))
    s.setup(SparseMatrix.from_scipy(sp))
    for lvl in s.precond.levels[:-1]:
        for leaf in jax.tree_util.tree_leaves(
            lvl.smoother.apply_params()
        ):
            if hasattr(leaf, "dtype") and np.issubdtype(
                np.dtype(leaf.dtype), np.floating
            ):
                assert np.dtype(leaf.dtype) == np.float32


def test_bf16_refined_converges():
    sp, b = _poisson()
    s = _solver(_refine_cfg("BFLOAT16", "ALL", "INEXACT"))
    s.setup(SparseMatrix.from_scipy(sp))
    for lvl in s.inner.precond.levels:
        assert str(lvl.A.values.dtype) == "bfloat16"
    res = s.solve(b)
    assert int(res.status) == 0
    x = np.asarray(res.x)
    assert np.linalg.norm(b - sp @ x) / np.linalg.norm(b) < 1e-8


def test_complex_hierarchy_skips_cast():
    sp, _ = _poisson((10, 10))
    spc = sp.astype(np.complex128)
    s = _solver(_amg_cfg(extra_amg='"hierarchy_dtype": "FLOAT32",'))
    s.setup(SparseMatrix.from_scipy(spc))
    for lvl in s.precond.levels:
        assert np.dtype(lvl.A.values.dtype).kind == "c"


# ---------------------------------------------------------------------
# inexact coarse solves


def test_inexact_coarse_parity():
    from amgx_tpu.solvers.inexact import InexactCoarseSolver

    sp, b = _poisson()
    A = SparseMatrix.from_scipy(sp)
    base = _solver(_amg_cfg("DENSE_LU_SOLVER"))
    base.setup(A)
    r0 = base.solve(b)
    inx = _solver(_amg_cfg("INEXACT"))
    inx.setup(A)
    r1 = inx.solve(b)
    cs = inx.precond.coarse_solver
    assert isinstance(cs, InexactCoarseSolver)
    assert cs.sweep_budget() <= cs.max_coarse_iters
    assert int(r0.status) == 0 and int(r1.status) == 0
    assert int(r1.iters) <= int(np.ceil(1.1 * int(r0.iters))) + 1


def test_inexact_sstep_method():
    from amgx_tpu.solvers.sstep import SStepPCGSolver

    sp, b = _poisson()
    s = _solver(_amg_cfg(
        "INEXACT",
        extra_amg='"inexact_coarse_solver": "SSTEP_PCG", "s_step": 2,',
    ))
    s.setup(SparseMatrix.from_scipy(sp))
    cs = s.precond.coarse_solver
    assert isinstance(cs.inner, SStepPCGSolver)
    # max_iters is an inner-step budget: s-step outers round up
    assert cs.inner.max_iters == -(-cs.sweep_budget() // 2)
    res = s.solve(b)
    assert int(res.status) == 0


def test_inexact_krylov_inner_defaults_unpreconditioned():
    """An unconfigured Krylov inner must NOT resolve the registry
    default preconditioner ("AMG") — that recursion built hierarchies
    all the way down."""
    sp, _ = _poisson((12, 12))
    s = _solver(_amg_cfg(
        "INEXACT",
        extra_amg='"inexact_coarse_solver": "SSTEP_PCG", "s_step": 2,',
    ))
    s.setup(SparseMatrix.from_scipy(sp))
    assert s.precond.coarse_solver.inner.precond is None


def test_flat_config_inexact_krylov_no_recursion():
    """A FLAT (legacy k=v) config names the outer PCG's AMG under the
    default-scope 'preconditioner' key; the INEXACT inner must not
    inherit it (review fix: that recursion built hierarchies on the
    coarsest level without bound)."""
    sp, b = _poisson((12, 12))
    cfg = AMGConfig.from_string(
        "solver=PCG, preconditioner=AMG, coarse_solver=INEXACT,"
        " inexact_coarse_solver=SSTEP_PCG, algorithm=AGGREGATION,"
        " selector=SIZE_8, min_coarse_rows=32, max_levels=10,"
        " monitor_residual=1, tolerance=1e-8,"
        " convergence=RELATIVE_INI"
    )
    s = make_nested(create_solver(cfg, "default"))
    s.setup(SparseMatrix.from_scipy(sp))  # must not recurse
    assert s.precond.coarse_solver.inner.precond is None
    assert int(s.solve(b).status) == 0


def test_inexact_scoped_preconditioner_honored():
    """A preconditioner in the inexact inner's OWN dedicated scope is
    kept (only default/outer-scope inheritance is severed)."""
    sp, _ = _poisson((12, 12))
    cfg = AMGConfig.from_string(
        '{"config_version": 2, "solver": {"scope": "main",'
        ' "solver": "PCG", "max_iters": 100, "tolerance": 1e-8,'
        ' "monitor_residual": 1, "convergence": "RELATIVE_INI",'
        ' "preconditioner": {"scope": "amg", "solver": "AMG",'
        ' "algorithm": "AGGREGATION", "selector": "SIZE_8",'
        ' "max_iters": 1, "monitor_residual": 0,'
        ' "min_coarse_rows": 32, "coarse_solver": "INEXACT",'
        ' "inexact_coarse_solver": {"scope": "cg",'
        '   "solver": "SSTEP_PCG", "s_step": 2,'
        '   "preconditioner": "BLOCK_JACOBI"}}}}'
    )
    s = make_nested(create_solver(cfg, "default"))
    s.setup(SparseMatrix.from_scipy(sp))
    pc = s.precond.coarse_solver.inner.precond
    assert pc is not None and pc.registry_name == "BLOCK_JACOBI"


def test_block_invert_diag_preserves_bf16():
    """Block-diagonal inversion must return the level dtype for
    sub-f32 hierarchies on BOTH the host and traced paths (review
    fix: numpy upcast to f64 / jnp.linalg.inv NotImplementedError)."""
    from amgx_tpu.ops.diagonal import invert_diag, invert_diag_jnp

    eye2 = sps.eye_array(2) * 3.0
    blocks = [[eye2 if i == j else None for j in range(4)]
              for i in range(4)]
    bs = sps.block_array(blocks).tocsr()
    Ab = SparseMatrix.from_scipy(bs, block_size=2).astype(jnp.bfloat16)
    assert str(invert_diag(Ab).dtype) == "bfloat16"
    assert str(jax.jit(invert_diag_jnp)(Ab).dtype) == "bfloat16"


def test_f64_spelling_on_f64_operator_never_falls_back():
    """hierarchy_dtype=FLOAT64 on an f64 operator is a no-op cast —
    the precision guardrail must stay inert even on a non-converged
    solve (review fix: the fallback would duplicate a bitwise-
    equivalent hierarchy)."""
    sp, b = _poisson((12, 12))
    cfg = AMGConfig.from_string(_refine_cfg(
        "FLOAT64", "ALL", "DENSE_LU_SOLVER",
        extra_outer='"refine_iteration_guard": 1,',
    ))
    cfg.set("max_iters", 1, "main")
    cfg.set("tolerance", 1e-14, "main")
    s = make_nested(create_solver(cfg, "default"))
    s.setup(SparseMatrix.from_scipy(sp))
    s.solve(b)
    assert s.precision_fallbacks == 0
    assert s._fallback_solver is None


def test_coarse_factor_profile_phase():
    sp, _ = _poisson()
    for coarse in ("DENSE_LU_SOLVER", "INEXACT"):
        s = _solver(_amg_cfg(coarse))
        s.setup(SparseMatrix.from_scipy(sp))
        prof = s.collect_setup_profile()
        assert "coarse_factor" in prof and prof["coarse_factor"] > 0
        # the split is out of finalize, not double-counted into it
        assert "finalize" in prof


def test_inexact_coarsens_past_the_dense_trigger():
    """Without the DenseLU stop trigger the hierarchy coarsens down to
    min_coarse_rows — the coarsest level is strictly smaller."""
    sp, _ = _poisson()
    A = SparseMatrix.from_scipy(sp)
    dense = _solver(_amg_cfg("DENSE_LU_SOLVER"))
    dense.setup(A)
    inx = _solver(_amg_cfg("INEXACT"))
    inx.setup(A)
    assert (
        inx.precond.levels[-1].n_rows
        <= dense.precond.levels[-1].n_rows
    )


# ---------------------------------------------------------------------
# astype / replace_values dtype propagation (satellite)


def test_astype_keeps_structure_memo_live_dtype():
    sp, _ = _poisson((12, 12))
    A = SparseMatrix.from_scipy(sp)
    fp = A.fingerprint()
    A32 = A.astype(np.float32)
    # memo survived the down-cast (no rehash)
    assert getattr(A32, "_fingerprint_cache") == fp
    # but the store identity reads the LIVE dtype
    assert A.setup_key() == (fp, "float64")
    assert A32.setup_key() == (fp, "float32")
    # identity cast returns self (object identity, memos intact)
    assert A.astype(np.float64) is A
    # a values-only swap on the cast matrix keeps dtype AND memo
    A32b = A32.replace_values(np.asarray(sp.data))  # f64 values in
    assert np.dtype(A32b.values.dtype) == np.float32
    assert getattr(A32b, "_fingerprint_cache") == fp
    assert A32b.setup_key() == (fp, "float32")


def test_astype_casts_accel_structures():
    sp, _ = _poisson((12, 12))
    A = SparseMatrix.from_scipy(sp)
    A32 = A.astype(np.float32)
    for name in ("values", "diag", "dia_vals", "ell_vals", "dense"):
        v = getattr(A32, name, None)
        if v is not None:
            assert np.dtype(v.dtype) == np.float32, name
    # index arrays untouched
    assert np.dtype(A32.col_indices.dtype) == np.int32


# ---------------------------------------------------------------------
# mixed-dtype store round-trips (satellite)


def test_mixed_store_roundtrip_bitwise(tmp_path):
    from amgx_tpu.amg.hierarchy import levels_bitwise_equal

    sp, b = _poisson()
    s = _solver(_refine_cfg("FLOAT32", "ALL", "INEXACT"))
    s.setup(SparseMatrix.from_scipy(sp))
    r_ref = s.solve(b)
    path = str(tmp_path / "mixed.npz")
    s.save_setup(path)
    s2 = Solver.load_setup(path)
    amg, amg2 = s.inner.precond, s2.inner.precond
    assert levels_bitwise_equal(amg, amg2) is None
    assert amg2.setup_stats["coarsen_calls"] == 0
    assert amg2.setup_stats["restored"]
    for lvl in amg2.levels:
        assert np.dtype(lvl.A.values.dtype) == np.float32
    r2 = s2.solve(b)
    assert int(r2.iters) == int(r_ref.iters)
    assert int(r2.status) == 0


def test_dense_lu_factors_persist_bitwise(tmp_path):
    from amgx_tpu.solvers.dense_lu import DenseLUSolver

    sp, b = _poisson()
    s = _solver(_amg_cfg("DENSE_LU_SOLVER"))
    s.setup(SparseMatrix.from_scipy(sp))
    path = str(tmp_path / "lu.npz")
    s.save_setup(path)
    calls = []
    orig = DenseLUSolver._setup_impl

    def counted(self, A):
        calls.append(1)
        return orig(self, A)

    DenseLUSolver._setup_impl = counted
    try:
        s2 = Solver.load_setup(path)
    finally:
        DenseLUSolver._setup_impl = orig
    # restore did NOT refactorize — the persisted factors are used
    assert not calls
    lu0 = np.asarray(s.precond.coarse_solver._params[1])
    lu1 = np.asarray(s2.precond.coarse_solver._params[1])
    assert np.array_equal(lu0, lu1)
    r2 = s2.solve(b)
    assert int(r2.status) == 0


def test_stale_f64_artifact_is_a_miss_not_a_hit(tmp_path):
    """An all-f64 payload whose manifest claims a mixed-precision
    config must fail typed (StoreError -> counted miss), never restore
    as a wrong-dtype hierarchy."""
    from amgx_tpu.core.errors import StoreError
    from amgx_tpu.store import serialize

    sp, _ = _poisson()
    s = _solver(_amg_cfg("DENSE_LU_SOLVER"))
    s.setup(SparseMatrix.from_scipy(sp))
    path = str(tmp_path / "f64.npz")
    s.save_setup(path)
    arrays, manifest = serialize.read_payload(path)
    cfg_mixed = AMGConfig.from_string(_amg_cfg(
        "DENSE_LU_SOLVER",
        extra_amg='"hierarchy_dtype": "F32", '
                  '"level_dtype_policy": "ALL",',
    ))
    manifest["config"] = cfg_mixed.to_state()
    manifest["config_hash"] = cfg_mixed.content_hash()
    stale = str(tmp_path / "stale.npz")
    serialize.write_payload(stale, dict(arrays), manifest)
    with pytest.raises(StoreError):
        Solver.load_setup(stale)


def test_mixed_keys_do_not_collide_in_store(tmp_path):
    """Same fingerprint, f64 vs mixed config: distinct store keys —
    the f64 artifact is a MISS for the mixed lookup (counted), never a
    wrong-dtype hit."""
    from amgx_tpu.store.store import ArtifactStore

    store = ArtifactStore(str(tmp_path / "store"))
    cfg64 = AMGConfig.from_string(_amg_cfg())
    cfg32 = AMGConfig.from_string(
        _amg_cfg(extra_amg='"hierarchy_dtype": "F32",')
    )
    fp = "deadbeef" * 4
    k64 = store.entry_key(fp, cfg64.content_hash(), "float64")
    k32 = store.entry_key(fp, cfg32.content_hash(), "float64")
    assert k64 != k32
    store.put(k64, {"a0": np.zeros(4)}, {"schema_version": 1})
    misses0 = store.stats().get("misses", 0)
    assert store.get(k32) is None
    assert store.stats().get("misses", 0) == misses0 + 1


def test_bf16_store_roundtrip_preserves_dtype(tmp_path):
    """npz degrades extension dtypes to raw void bytes; the serialize
    shim must bring bfloat16 leaves back as bfloat16."""
    from amgx_tpu.store import serialize

    a = np.arange(12, dtype=np.float32).astype(jnp.bfloat16)
    tree = {"v": a, "dev": jnp.asarray(a), "f": np.ones(3)}
    spec, arrays = serialize.flatten(tree)
    path = str(tmp_path / "bf16.npz")
    serialize.write_payload(path, arrays, {"spec": spec,
                                           "schema_version": 1})
    raw, manifest = serialize.read_payload(path)
    out = serialize.unflatten(manifest["spec"], raw)
    assert str(np.dtype(out["v"].dtype)) == "bfloat16"
    assert str(np.dtype(out["dev"].dtype)) == "bfloat16"
    assert np.array_equal(
        np.asarray(out["v"], np.float32), np.asarray(a, np.float32)
    )
    assert np.dtype(out["f"].dtype) == np.float64


# ---------------------------------------------------------------------
# refinement guardrail + accounting


def test_refinement_inner_iteration_accounting():
    sp, b = _poisson()
    s = _solver(_refine_cfg("FLOAT32", "ALL", "INEXACT"))
    s.setup(SparseMatrix.from_scipy(sp))
    res = s.solve(b)
    assert int(res.status) == 0
    # unmonitored inner PCG retires exactly max_iters=8 per outer
    assert s.last_inner_iters == int(res.iters) * 8


def test_precision_fallback_guardrail_trips_and_recovers():
    sp, b = _poisson()
    s = _solver(_refine_cfg(
        "FLOAT32", "ALL", "INEXACT",
        extra_outer='"precision_fallback": 1, '
                    '"refine_iteration_guard": 1,',
    ))
    s.setup(SparseMatrix.from_scipy(sp))
    res = s.solve(b)
    assert s.precision_fallbacks == 1
    assert int(res.status) == 0
    # the fallback hierarchy really is full precision
    for lvl in s._fallback_solver.inner.precond.levels:
        assert np.dtype(lvl.A.values.dtype) == np.float64
    x = np.asarray(res.x)
    assert np.linalg.norm(b - sp @ x) / np.linalg.norm(b) < 1e-8


def test_precision_fallback_disarmed():
    sp, b = _poisson()
    s = _solver(_refine_cfg(
        "FLOAT32", "ALL", "INEXACT",
        extra_outer='"precision_fallback": 0, '
                    '"refine_iteration_guard": 1,',
    ))
    s.setup(SparseMatrix.from_scipy(sp))
    s.solve(b)
    assert s.precision_fallbacks == 0
    assert s._fallback_solver is None


def test_all_f64_refinement_never_falls_back():
    """Behavior guard: without hierarchy_dtype the guardrail is inert
    even on a non-converged solve."""
    sp, b = _poisson((12, 12))
    cfg = AMGConfig.from_string(_refine_cfg(
        "SAME", "ALL", "DENSE_LU_SOLVER",
        extra_outer='"refine_iteration_guard": 1,',
    ))
    cfg.set("max_iters", 1, "main")
    s = make_nested(create_solver(cfg, "default"))
    s.setup(SparseMatrix.from_scipy(sp))
    s.solve(b)
    assert s.precision_fallbacks == 0


# ---------------------------------------------------------------------
# serve: batch parity + telemetry bytes


def _jittered_family(shape, count, seed=1, jitter=0.05):
    rng = np.random.default_rng(seed)
    base = poisson_scipy(shape).tocsr()
    base.sort_indices()
    out = []
    for _ in range(count):
        spi = base.copy()
        spi.data = spi.data * (
            1.0 + jitter * rng.standard_normal(spi.data.shape)
        )
        spi = ((spi + spi.T) * 0.5).tocsr()
        spi = (spi + sps.diags_array(
            np.abs(spi).sum(axis=1).ravel()
            - np.abs(spi.diagonal()) - spi.diagonal() + 0.1
        )).tocsr()
        spi.sort_indices()
        out.append((spi, rng.standard_normal(spi.shape[0])))
    return out


@pytest.mark.serve
@pytest.mark.parametrize(
    "mode,cfg_text",
    [
        ("mixed_f32", _amg_cfg(
            extra_amg='"hierarchy_dtype": "F32", '
                      '"level_dtype_policy": "ALL",',
            outer_tol=1e-8,
        )),
        ("inexact", _amg_cfg("INEXACT", outer_tol=1e-8)),
        ("cheap_refined", None),  # CHEAP_PRECONDITIONER_CONFIG
    ],
)
def test_batched_group_parity_cheap_modes(mode, cfg_text):
    """The two new modes (and their refinement-wrapped composition)
    batch through the vmapped serve path and match the sequential
    values-only resetup reference iteration-for-iteration."""
    from amgx_tpu.serve import (
        CHEAP_PRECONDITIONER_CONFIG,
        BatchedSolveService,
    )

    if cfg_text is None:
        cfg_text = CHEAP_PRECONDITIONER_CONFIG
    systems = _jittered_family((16, 16), 6)
    svc = BatchedSolveService(config=cfg_text, max_batch=8)
    results = svc.solve_many(systems)
    m = svc.metrics.snapshot()
    assert m["batches"] == 1
    assert m.get("fallback_solves", 0) == 0
    assert m.get("quarantines", 0) == 0
    s = _solver(cfg_text)
    s.setup(SparseMatrix.from_scipy(systems[0][0]))
    for (spi, bi), r in zip(systems, results):
        s.resetup(SparseMatrix.from_scipy(spi))
        ref = s.solve(bi)
        assert int(r.status) == 0
        assert int(r.iters) == int(ref.iters)
        xr = np.asarray(ref.x)
        err = np.linalg.norm(np.asarray(r.x) - xr) / max(
            np.linalg.norm(xr), 1e-300
        )
        assert err < 1e-6


@pytest.mark.serve
def test_hierarchy_bytes_by_dtype_telemetry():
    from amgx_tpu import telemetry
    from amgx_tpu.serve import BatchedSolveService

    systems = _jittered_family((16, 16), 4)
    svc = BatchedSolveService(
        config=_amg_cfg(
            extra_amg='"hierarchy_dtype": "F32", '
                      '"level_dtype_policy": "ALL",',
            outer_tol=1e-8,
        ),
        max_batch=8,
    )
    svc.solve_many(systems)
    hb = svc.cache.bytes_by_dtype()
    assert hb.get("float32", 0) > 0
    # the mixed hierarchy's value mass moves from f64 to f32 (the
    # template operator itself stays at the upload dtype): against the
    # same hierarchy at f64, every value byte is there at half width
    plain = BatchedSolveService(config=_amg_cfg(outer_tol=1e-8),
                                max_batch=8)
    plain.solve_many(systems)
    hb64 = plain.cache.bytes_by_dtype()
    assert hb64.get("float32", 0) == 0
    assert 2 * hb["float32"] == hb64["float64"]
    assert hb.get("float64", 0) < hb64["float64"]
    snap = svc.telemetry_snapshot()
    assert snap["hierarchy_bytes"] == hb
    text = telemetry.get_registry().render_prometheus()
    assert 'amgx_cache_hierarchy_bytes{' in text
    assert 'dtype="float32"' in text


def test_cheap_preconditioner_config_parses():
    from amgx_tpu.serve import CHEAP_PRECONDITIONER_CONFIG

    cfg = AMGConfig.from_string(CHEAP_PRECONDITIONER_CONFIG)
    s = make_nested(create_solver(cfg, "default"))
    sp, b = _poisson((16, 16))
    s.setup(SparseMatrix.from_scipy(sp))
    res = s.solve(b)
    assert int(res.status) == 0
