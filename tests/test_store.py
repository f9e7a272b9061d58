"""Setup-artifact store tests (amgx_tpu.store): save/load round trips
across dtypes and block sizes, corrupt/stale-schema fallback, LRU
budgets, warm-boot serving, and the capi solver_save/solver_load
surface.

The load-bearing contract: a restored solver solves with ITERATION
COUNTS IDENTICAL to a freshly-set-up one, and restoring skips setup
entirely (asserted via the AMG setup counters, not timing)."""

import json
import os

import numpy as np
import pytest

import amgx_tpu
from amgx_tpu.config.amg_config import AMGConfig
from amgx_tpu.core.errors import StoreError
from amgx_tpu.io.poisson import (
    jittered_poisson_family,
    poisson_2d_5pt,
    poisson_rhs,
)
from amgx_tpu.solvers import create_solver
from amgx_tpu.solvers.base import SUCCESS, Solver
from amgx_tpu.store import ArtifactStore
from amgx_tpu.store import serialize as ser

amgx_tpu.initialize()

PCG_AMG = """
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

AMG_STANDALONE = """
{"config_version": 2,
 "solver": {"scope": "main", "solver": "AMG", "algorithm": "CLASSICAL",
    "selector": "PMIS", "smoother": {"scope": "jac",
        "solver": "BLOCK_JACOBI", "relaxation_factor": 0.8,
        "monitor_residual": 0},
    "presweeps": 2, "postsweeps": 2, "max_levels": 20,
    "min_coarse_rows": 16, "coarse_solver": "DENSE_LU_SOLVER",
    "cycle": "V", "max_iters": 40, "monitor_residual": 1,
    "convergence": "RELATIVE_INI", "tolerance": 1e-08, "norm": "L2"}}
"""

JAC_PCG = """
{"config_version": 2,
 "solver": {"scope": "main", "solver": "PCG", "max_iters": 200,
    "tolerance": 1e-8, "monitor_residual": 1,
    "convergence": "RELATIVE_INI",
    "preconditioner": {"scope": "jac", "solver": "BLOCK_JACOBI",
        "relaxation_factor": 0.9, "max_iters": 2,
        "monitor_residual": 0}}}
"""


def _setup_solver(cfg_text, A):
    s = create_solver(AMGConfig.from_string(cfg_text), "default")
    s.setup(A)
    return s


def _amg_of(solver):
    """The AMG solver inside a solver tree (self or preconditioner)."""
    from amgx_tpu.amg.hierarchy import AMGSolver

    if isinstance(solver, AMGSolver):
        return solver
    return solver.precond


# ---------------------------------------------------------------------------
# save/load round trips


def test_amg_roundtrip_identical_and_skips_setup(tmp_path):
    A = poisson_2d_5pt(32)
    b = poisson_rhs(A.n_rows)
    s = _setup_solver(AMG_STANDALONE, A)
    res1 = s.solve(b)
    assert s.setup_stats["coarsen_calls"] >= 1

    path = tmp_path / "amg.npz"
    manifest = s.save_setup(path)
    assert manifest["schema_version"] == ser.SCHEMA_VERSION
    assert manifest["fingerprint"] == A.fingerprint()

    s2 = Solver.load_setup(path)
    # restore skipped setup ENTIRELY: no coarsening ran, the setup
    # timer never started, and the restore timer did
    assert s2.setup_stats["coarsen_calls"] == 0
    assert s2.setup_stats["levels_built"] == 0
    assert s2.setup_stats["restored"] is True
    assert s2.setup_time == 0.0
    assert s2.restore_time > 0.0
    assert len(s2.levels) == len(s.levels)

    res2 = s2.solve(b)
    assert int(res2.iters) == int(res1.iters)
    assert int(res2.status) == int(res1.status)
    assert np.array_equal(np.asarray(res2.x), np.asarray(res1.x))


@pytest.mark.parametrize("dtype", [np.float32, np.float64])
def test_pcg_amg_roundtrip_dtypes(tmp_path, dtype):
    A = poisson_2d_5pt(24, dtype=dtype)
    b = poisson_rhs(A.n_rows, dtype=dtype)
    s = _setup_solver(PCG_AMG, A)
    res1 = s.solve(b)
    assert int(res1.status) == SUCCESS

    path = tmp_path / "pcg_amg.npz"
    s.save_setup(path)
    s2 = Solver.load_setup(path)
    assert _amg_of(s2).setup_stats["coarsen_calls"] == 0
    assert np.dtype(s2.A.values.dtype) == np.dtype(dtype)
    res2 = s2.solve(b)
    assert int(res2.iters) == int(res1.iters)
    assert int(res2.status) == int(res1.status)
    assert np.array_equal(np.asarray(res2.x), np.asarray(res1.x))


def test_block_matrix_roundtrip(tmp_path, rng):
    from tests.conftest import random_csr

    from amgx_tpu.core.matrix import SparseMatrix

    sp = random_csr(48, density=0.12, seed=3, spd=True)
    A = SparseMatrix.from_scipy(sp, block_size=2)
    b = rng.standard_normal(A.n_rows * 2)
    s = _setup_solver(JAC_PCG, A)
    res1 = s.solve(b)

    path = tmp_path / "block.npz"
    s.save_setup(path)
    s2 = Solver.load_setup(path)
    assert s2.A.block_size == 2
    res2 = s2.solve(b)
    assert int(res2.iters) == int(res1.iters)
    assert np.array_equal(np.asarray(res2.x), np.asarray(res1.x))


def test_matrix_leaves_bitwise_and_shared(tmp_path):
    """Every array leaf of every level restores bitwise, including the
    rehydrated acceleration structures (diag/ell/dia/dense + gather
    maps), and object sharing survives (the PCG's operator IS its
    AMG's finest-level operator, not a copy)."""
    A = poisson_2d_5pt(48)
    s = _setup_solver(PCG_AMG, A)
    path = tmp_path / "leaves.npz"
    s.save_setup(path)
    s2 = Solver.load_setup(path)
    assert s2.A is s2.precond.A  # dedup restored the sharing
    fields = (
        "row_offsets", "col_indices", "values", "row_ids", "diag",
        "ell_cols", "ell_vals", "dia_vals", "dense",
        "diag_src", "dia_src", "ell_src",
    )
    seen_accel = set()
    for l1, l2 in zip(s.precond.levels, s2.precond.levels):
        for o1, o2 in ((l1.A, l2.A), (l1.P, l2.P), (l1.R, l2.R)):
            if o1 is None:
                assert o2 is None
                continue
            assert o1.dia_offsets == o2.dia_offsets
            for f in fields:
                v1, v2 = getattr(o1, f), getattr(o2, f)
                if v1 is None:
                    assert v2 is None, f
                    continue
                seen_accel.add(f)
                assert np.array_equal(
                    np.asarray(v1), np.asarray(v2)
                ), f
            assert o1.fingerprint() == o2.fingerprint()
    # the hierarchy actually exercised the accel formats this test
    # claims to cover
    assert {"dia_vals", "ell_vals", "dense"} & seen_accel


def test_cheb_smoothed_amg_restore_skips_estimation(
    tmp_path, monkeypatch
):
    """Per-level smoother state persists: a Chebyshev-smoothed AMG
    hierarchy restores its spectrum bounds instead of re-running the
    power iteration per level."""
    cfg_text = AMG_STANDALONE.replace(
        '"solver": "BLOCK_JACOBI"', '"solver": "CHEBYSHEV"'
    )
    A = poisson_2d_5pt(24)
    b = poisson_rhs(A.n_rows)
    s = _setup_solver(cfg_text, A)
    res1 = s.solve(b)
    bounds = [
        (lvl.smoother.lmax, lvl.smoother.lmin)
        for lvl in s.levels
        if lvl.smoother is not None
    ]
    path = tmp_path / "cheb.npz"
    s.save_setup(path)

    from amgx_tpu.solvers.chebyshev import ChebyshevSolver

    def boom(*a, **k):
        raise AssertionError("restore must not re-estimate lambda")

    monkeypatch.setattr(ChebyshevSolver, "_estimate_lambda_max", boom)
    s2 = Solver.load_setup(path)
    bounds2 = [
        (lvl.smoother.lmax, lvl.smoother.lmin)
        for lvl in s2.levels
        if lvl.smoother is not None
    ]
    assert bounds2 == bounds
    res2 = s2.solve(b)
    assert int(res2.iters) == int(res1.iters)
    assert np.array_equal(np.asarray(res2.x), np.asarray(res1.x))


def test_scaled_reordered_solver_roundtrip(tmp_path):
    """The solve-boundary scale/reorder vectors restore with the
    setup: a scaled+RCM-reordered solver round-trips to identical
    results."""
    cfg_text = """
    {"config_version": 2,
     "solver": {"scope": "main", "solver": "PCG", "max_iters": 200,
        "tolerance": 1e-8, "monitor_residual": 1,
        "convergence": "RELATIVE_INI", "scaling": "DIAGONAL_SYMMETRIC",
        "matrix_reordering": "RCM",
        "preconditioner": {"scope": "jac", "solver": "BLOCK_JACOBI",
            "relaxation_factor": 0.9, "max_iters": 2,
            "monitor_residual": 0}}}
    """
    A = poisson_2d_5pt(20)
    b = poisson_rhs(A.n_rows)
    s = _setup_solver(cfg_text, A)
    assert s._scale_vecs is not None
    res1 = s.solve(b)

    path = tmp_path / "scaled.npz"
    s.save_setup(path)
    s2 = Solver.load_setup(path)
    assert s2._scale_vecs is not None
    for v1, v2 in zip(s._scale_vecs, s2._scale_vecs):
        assert np.array_equal(np.asarray(v1), np.asarray(v2))
    # RCM adoption is a backend heuristic; restore must MATCH the
    # original either way
    assert (s2._reorder is None) == (s._reorder is None)
    res2 = s2.solve(b)
    assert int(res2.iters) == int(res1.iters)
    assert np.array_equal(np.asarray(res2.x), np.asarray(res1.x))


def _windowed_matrix(monkeypatch):
    """An unstructured (ELL, not DIA) matrix that takes the windowed
    arrays: the backend gate forced as on a TPU."""
    import scipy.sparse as sps

    from amgx_tpu.core.matrix import SparseMatrix

    monkeypatch.setenv("AMGX_TPU_TILED_ELL", "1")
    n = 6000
    rng = np.random.default_rng(21)
    r = np.repeat(np.arange(n), 4)
    c = np.abs(r + rng.integers(-300, 301, r.shape))
    c = np.where(c >= n, 2 * (n - 1) - c, c)  # reflected at the ends
    m = sps.coo_matrix((rng.standard_normal(r.shape) * 0.1, (r, c)),
                       shape=(n, n)).tocsr()
    m = (m + m.T + sps.eye_array(n) * 8.0).tocsr()
    m.sort_indices()
    A = SparseMatrix.from_scipy(m, dtype=np.float32)
    assert A.ell_wrows is not None and not A.has_dia
    return A


def test_windowed_ell_roundtrip(tmp_path, monkeypatch):
    """The windowed-ELL arrays, touched-row lists and counts included,
    restore bitwise, and the restored solver solves identically."""
    A = _windowed_matrix(monkeypatch)
    b = np.random.default_rng(22).standard_normal(A.n_rows).astype(
        np.float32)
    s = _setup_solver(JAC_PCG, A)
    res1 = s.solve(b)
    path = tmp_path / "windowed.npz"
    s.save_setup(path)
    s2 = Solver.load_setup(path)
    assert s2.A.ell_wwidth == s.A.ell_wwidth
    for f in ("ell_wcols", "ell_wvals", "ell_wbase", "ell_wrows",
              "ell_wcount"):
        assert np.array_equal(np.asarray(getattr(s.A, f)),
                              np.asarray(getattr(s2.A, f))), f
    res2 = s2.solve(b)
    assert int(res2.iters) == int(res1.iters)
    assert np.array_equal(np.asarray(res2.x), np.asarray(res1.x))


def test_windowed_store_without_touched_rows_loads(monkeypatch):
    """A store written before the touched-row lists existed loads: the
    matrix drops its windowed arrays and rides the plain ELL arrays."""
    A = _windowed_matrix(monkeypatch)
    spec, arrays = ser.flatten(A)
    spec = json.loads(json.dumps(spec))
    fields = (spec["n"] if spec["t"] == "def" else spec)["fields"]
    del fields["ell_wrows"], fields["ell_wcount"]
    B = ser.unflatten(spec, ser.materialize(arrays))
    assert B.ell_wcols is None and B.ell_wwidth is None
    assert B.has_ell
    x = np.random.default_rng(23).standard_normal(A.n_rows).astype(
        np.float32)
    np.testing.assert_allclose(np.asarray(B.to_scipy() @ x),
                               np.asarray(A.to_scipy() @ x), rtol=1e-6)


def test_load_missing_or_not_a_payload(tmp_path):
    with pytest.raises(StoreError):
        Solver.load_setup(tmp_path / "nope.npz")
    bad = tmp_path / "garbage.npz"
    bad.write_bytes(b"definitely not an npz payload")
    with pytest.raises(StoreError):
        Solver.load_setup(bad)


def test_schema_version_bump_rejected(tmp_path):
    A = poisson_2d_5pt(16)
    s = _setup_solver(JAC_PCG, A)
    path = tmp_path / "v.npz"
    s.save_setup(path)
    arrays, manifest = ser.read_payload(str(path))
    manifest["schema_version"] = ser.SCHEMA_VERSION + 1
    ser.write_payload(path, arrays, manifest)
    with pytest.raises(StoreError):
        Solver.load_setup(path)


def test_config_hash_covers_scope_links():
    """Two configs with identical key/value maps but different
    sub-solver scope links resolve different parameters and must hash
    differently — they key hierarchies in the persistent store."""
    base = AMGConfig.from_string(JAC_PCG)
    linked = AMGConfig.from_state(base.to_state())
    assert linked.content_hash() == base.content_hash()
    # redirect the preconditioner's scope link only (values untouched)
    (key,) = [
        k for k in linked._scope_links if k[1] == "preconditioner"
    ]
    linked._scope_links[key] = "somewhere_else"
    assert linked.content_hash() != base.content_hash()


def test_config_mismatch_rejected(tmp_path):
    A = poisson_2d_5pt(16)
    s = _setup_solver(JAC_PCG, A)
    path = tmp_path / "c.npz"
    s.save_setup(path)
    other = AMGConfig.from_string(PCG_AMG)
    with pytest.raises(StoreError):
        Solver.load_setup(path, cfg=other)
    # matching config passes
    same = AMGConfig.from_string(JAC_PCG)
    assert Solver.load_setup(path, cfg=same).A is not None


# ---------------------------------------------------------------------------
# ArtifactStore behavior


def _toy_entry(i=0, kb=64):
    arrays = {"x": np.full(kb * 128, float(i))}  # kb KiB of f64
    manifest = {"kind": "toy", "i": i}
    return arrays, manifest


def test_store_put_get_roundtrip(tmp_path):
    st = ArtifactStore(tmp_path)
    key = st.entry_key("fp", "cfg", "float64")
    assert st.get(key) is None
    assert st.stats()["misses"] == 1
    arrays, manifest = _toy_entry(7)
    assert st.put(key, arrays, manifest)
    got = st.get(key)
    assert got is not None
    m, a = got
    assert m["i"] == 7
    assert np.array_equal(a["x"], arrays["x"])
    assert st.stats()["hits"] == 1


def test_store_corrupt_payload_is_miss(tmp_path):
    st = ArtifactStore(tmp_path)
    key = st.entry_key("fp", "cfg", "float64")
    st.put(key, *_toy_entry())
    npz = os.path.join(st.root, key + ".npz")
    blob = bytearray(open(npz, "rb").read())
    blob[len(blob) // 2] ^= 0xFF  # single-bit-ish rot
    open(npz, "wb").write(bytes(blob))
    assert st.get(key) is None  # miss, not an exception
    stats = st.stats()
    assert stats["corrupt_entries"] == 1
    assert stats["misses"] >= 1
    # corrupt entry was dropped from disk
    assert not os.path.exists(npz)


def test_store_truncated_payload_is_miss(tmp_path):
    st = ArtifactStore(tmp_path)
    key = st.entry_key("fp2", "cfg", "float64")
    st.put(key, *_toy_entry())
    npz = os.path.join(st.root, key + ".npz")
    blob = open(npz, "rb").read()
    open(npz, "wb").write(blob[: len(blob) // 3])
    assert st.get(key) is None
    assert st.stats()["corrupt_entries"] == 1


def test_store_stale_schema_is_miss(tmp_path):
    st = ArtifactStore(tmp_path)
    key = st.entry_key("fp3", "cfg", "float64")
    st.put(key, *_toy_entry())
    side_path = os.path.join(st.root, key + ".json")
    side = json.loads(open(side_path).read())
    side["schema_version"] = ser.SCHEMA_VERSION + 1
    open(side_path, "w").write(json.dumps(side))
    assert st.get(key) is None
    assert st.stats()["stale_schema"] == 1
    # scans skip it too
    assert list(st.entries()) == []


def test_store_budget_never_wipes_newest(tmp_path):
    """A payload bigger than the whole budget must not wipe the store:
    older entries evict, the newest survives (counted overflow)."""
    st = ArtifactStore(tmp_path, max_bytes=10 * 1024)  # < one entry
    k1 = st.entry_key("a", "c", "f8")
    st.put(k1, *_toy_entry(1))
    assert st.get(k1) is not None  # oversized but retained
    k2 = st.entry_key("b", "c", "f8")
    os.utime(os.path.join(st.root, k1 + ".npz"), (1000.0, 1000.0))
    os.utime(os.path.join(st.root, k1 + ".json"), (1000.0, 1000.0))
    st.put(k2, *_toy_entry(2))
    assert st.get(k2) is not None  # newest survives
    assert st.get(k1) is None  # older evicted under pressure
    assert st.stats().get("budget_overflows", 0) >= 1


def test_store_lru_eviction_under_budget(tmp_path):
    # each toy entry is ~64 KiB; budget fits two
    st = ArtifactStore(tmp_path, max_bytes=150 * 1024)
    keys = [st.entry_key(f"fp{i}", "cfg", "f8") for i in range(3)]
    for i, k in enumerate(keys):
        st.put(k, *_toy_entry(i))
        os.utime(
            os.path.join(st.root, k + ".npz"), (1000.0 + i, 1000.0 + i)
        )
        os.utime(
            os.path.join(st.root, k + ".json"), (1000.0 + i, 1000.0 + i)
        )
    st._enforce_budget()
    assert st.stats()["evictions"] >= 1
    # the OLDEST entry went first; the newest survives
    assert st.get(keys[2]) is not None
    assert st.get(keys[0]) is None


# ---------------------------------------------------------------------------
# warm-boot serving


def _serve_systems(shape=(16, 16), count=8):
    return jittered_poisson_family(shape, count, seed=0)


def test_warmboot_service_serves_from_store(tmp_path):
    from amgx_tpu.serve import BatchedSolveService

    systems = _serve_systems()
    svc1 = BatchedSolveService(max_batch=8, store=str(tmp_path))
    res1 = svc1.solve_many(systems)
    assert all(int(r.status) == SUCCESS for r in res1)
    svc1.flush_store()
    m1 = svc1.metrics.snapshot()
    assert m1.get("store_exports", 0) >= 1
    assert len(svc1.store) >= 1

    # a FRESH service (new process stand-in) warm-boots from the store
    svc2 = BatchedSolveService(max_batch=8, store=str(tmp_path))
    assert svc2.warm_boot() >= 1
    res2 = svc2.solve_many(systems)
    m2 = svc2.metrics.snapshot()
    # first group for the persisted fingerprint: HIT, no rebuild
    assert m2.get("cache_hits", 0) >= 1
    assert m2.get("cache_misses", 0) == 0
    assert m2.get("setups", 0) == 0
    assert m2.get("warmboot_restores", 0) >= 1
    for r1, r2 in zip(res1, res2):
        assert int(r1.iters) == int(r2.iters)
        assert int(r1.status) == int(r2.status)


def test_warmboot_corrupt_entry_falls_back_to_fresh_setup(tmp_path):
    from amgx_tpu.serve import BatchedSolveService

    systems = _serve_systems()
    svc1 = BatchedSolveService(max_batch=8, store=str(tmp_path))
    svc1.solve_many(systems)
    svc1.flush_store()
    # corrupt every payload in the store
    for name in os.listdir(svc1.store.root):
        if name.endswith(".npz"):
            p = os.path.join(svc1.store.root, name)
            open(p, "wb").write(b"rotten")

    svc2 = BatchedSolveService(max_batch=8, store=str(tmp_path))
    assert svc2.warm_boot() == 0
    m = svc2.metrics.snapshot()
    assert m.get("warmboot_failures", 0) >= 1
    # service still healthy: fresh setup, correct answers
    res = svc2.solve_many(systems)
    assert all(int(r.status) == SUCCESS for r in res)
    assert svc2.metrics.snapshot().get("setups", 0) == 1


def test_warmboot_ignores_other_config(tmp_path):
    from amgx_tpu.serve import BatchedSolveService

    systems = _serve_systems()
    svc1 = BatchedSolveService(max_batch=8, store=str(tmp_path))
    svc1.solve_many(systems)
    svc1.flush_store()
    svc_other = BatchedSolveService(
        config=PCG_AMG, max_batch=8, store=str(tmp_path)
    )
    assert svc_other.warm_boot() == 0


# ---------------------------------------------------------------------------
# satellite: hierarchy-cache eviction drops orphaned executables


def test_hierarchy_evict_drops_compile_entries():
    from amgx_tpu.serve import BatchedSolveService

    svc = BatchedSolveService(max_batch=4, cache_entries=1)
    a_sys = _serve_systems(shape=(8, 8), count=4)
    b_sys = _serve_systems(shape=(12, 12), count=4)
    svc.solve_many(a_sys)
    assert len(svc.compile_cache) >= 1
    n_before = len(svc.compile_cache)
    svc.solve_many(b_sys)  # evicts pattern A's hierarchy entry
    m = svc.metrics.snapshot()
    assert m.get("cache_evictions", 0) >= 1
    assert m.get("compile_evictions", 0) >= 1
    # A's executables are gone; only B's (and nothing orphaned) remain
    assert len(svc.compile_cache) <= n_before + 1 - 1


def test_evict_signature_tombstones_inflight_warmups():
    """An executable whose warm-up finishes AFTER its signature was
    evicted must not be re-inserted (it would leak until process
    exit); a later get() for the signature clears the tombstone."""
    import concurrent.futures
    from types import SimpleNamespace

    from amgx_tpu.serve.cache import CompileCache

    cc = CompileCache()
    cc._compile = lambda entry, Bb: ("FN", Bb)
    entry = SimpleNamespace(signature="S")

    # executable present + an in-flight warm-up for the same signature
    cc._fns[("S", 4)] = ("FN", 4)
    fut = concurrent.futures.Future()
    cc._futures[("S", 8)] = fut
    assert cc.evict_signature("S") == 1
    assert cc.metrics.get("compile_evictions") == 1
    # the in-flight compile completes: waiters get the result, but the
    # executable is NOT retained
    cc._resolve(("S", 8), entry, 8, fut)
    assert fut.result() == ("FN", 8)
    assert len(cc) == 0
    # the signature coming back to life clears the tombstone
    assert cc.get(entry, 8) == ("FN", 8)
    assert len(cc) == 1


# ---------------------------------------------------------------------------
# satellite: fingerprint/dtype memo safety on values-only swaps


def test_fingerprint_memo_propagates_and_dtype_stays_live(tmp_path):
    from amgx_tpu.core.matrix import SparseMatrix, sparsity_fingerprint

    A = poisson_2d_5pt(16)
    fp = A.fingerprint()
    # values-only swap: structure memo rides along, stays correct
    A2 = A.replace_values(np.asarray(A.values) * 2.0)
    assert getattr(A2, "_fingerprint_cache", None) == fp
    assert A2.fingerprint() == sparsity_fingerprint(
        np.asarray(A2.row_offsets), np.asarray(A2.col_indices),
        A2.n_rows, A2.n_cols, A2.block_size,
    )
    # dtype half of the store key is read live — astype can't serve a
    # stale dtype
    A3 = A.astype(np.float32)
    assert A3.setup_key() == (fp, "float32")
    assert A.setup_key() == (fp, "float64")

    # a RESTORED matrix (fingerprint memo injected from the manifest)
    # then values-swapped must still serve the correct fingerprint
    s = _setup_solver(JAC_PCG, A)
    path = tmp_path / "memo.npz"
    s.save_setup(path)
    s2 = Solver.load_setup(path)
    R = s2.A
    assert getattr(R, "_fingerprint_cache", None) == fp
    R2 = R.replace_values(np.asarray(R.values) * 3.0)
    assert R2.fingerprint() == fp
    assert R2.setup_key() == (fp, "float64")


# ---------------------------------------------------------------------------
# capi surface


def test_capi_solver_save_load(tmp_path):
    from amgx_tpu.api import capi

    capi.initialize()
    cfg = capi.config_create(PCG_AMG)
    res = capi.resources_create_simple(cfg)
    from amgx_tpu.io.poisson import poisson_scipy

    sp = poisson_scipy((24, 24)).tocsr()
    n = sp.shape[0]
    mtx = capi.matrix_create(res, "dDDI")
    capi.matrix_upload_all(
        mtx, n, sp.nnz, 1, 1, sp.indptr, sp.indices, sp.data, None
    )
    rhs = capi.vector_create(res, "dDDI")
    sol = capi.vector_create(res, "dDDI")
    b = poisson_rhs(n)
    capi.vector_upload(rhs, n, 1, b)
    capi.vector_set_zero(sol, n, 1)
    slv = capi.solver_create(res, "dDDI", cfg)
    capi.solver_setup(slv, mtx)
    capi.solver_solve(slv, rhs, sol)
    iters = capi.solver_get_iterations_number(slv)

    path = str(tmp_path / "capi_setup.npz")
    assert capi.solver_save(slv, path) == capi.RC_OK

    slv2 = capi.solver_create(res, "dDDI", cfg)
    assert capi.solver_load(slv2, path) == capi.RC_OK
    sol2 = capi.vector_create(res, "dDDI")
    capi.vector_set_zero(sol2, n, 1)
    capi.solver_solve(slv2, rhs, sol2)
    assert capi.solver_get_iterations_number(slv2) == iters
    assert capi.solver_get_status(slv2) == capi.SOLVE_SUCCESS
    assert np.array_equal(
        capi.vector_download(sol), capi.vector_download(sol2)
    )
    # restore really skipped setup
    s2 = capi._get(slv2, capi._SolverHandle).solver
    assert _amg_of(s2).setup_stats["coarsen_calls"] == 0

    # loading under a DIFFERENT config is a typed RC, not a wrong answer
    cfg_other = capi.config_create(JAC_PCG)
    slv3 = capi.solver_create(res, "dDDI", cfg_other)
    with pytest.raises(capi.AMGXError):
        capi.solver_load(slv3, path)

    # saving an un-set-up solver is a typed RC too
    slv4 = capi.solver_create(res, "dDDI", cfg)
    with pytest.raises(capi.AMGXError):
        capi.solver_save(slv4, str(tmp_path / "x.npz"))

    # a handle whose MODE dtype differs from the persisted setup must
    # refuse (RC_BAD_MODE) — a mixed-precision hierarchy would break
    # the identical-iterations contract silently
    slv5 = capi.solver_create(res, "dFFI", cfg)
    with pytest.raises(capi.AMGXError) as ei:
        capi.solver_load(slv5, path)
    assert ei.value.rc == capi.RC_BAD_MODE

    # a pre-load batch must not masquerade as the restored solver's
    # results: solver_load settles it and clears the batch state
    capi.solver_solve_batch(slv, [mtx], [rhs], [sol])
    capi.solver_load(slv, path)
    with pytest.raises(capi.AMGXError):
        capi.solver_get_batch_status(slv, 0)
    with pytest.raises(capi.AMGXError):
        capi.solver_get_status(slv)  # no solve by the restored solver
