"""The program's classical AMG (AHAT strength, PMIS, D1, Galerkin, V(1,1)
with damped Jacobi and a dense coarse solve) against the plain reference
in tests/classical_reference.py, which shares none of its code.

Both of the program's builders are held to it: the host builder
(``setup_location`` HOST, amg/classical.py) and the device pipeline
(DEVICE, amg/device_setup.py), which until now were only compared with
each other.  PMIS takes the program's hash weights: with the same random
numbers the split has to be the same.
"""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
import scipy.sparse as sps
import scipy.sparse.linalg as spla

import amgx_tpu
from amgx_tpu.amg import classical as host
from amgx_tpu.amg import device_setup as dev
from amgx_tpu.config.amg_config import AMGConfig
from amgx_tpu.core.matrix import SparseMatrix
from amgx_tpu.io.poisson import poisson_scipy
from amgx_tpu.solvers import create_solver

import classical_reference as ref

amgx_tpu.initialize()

THETA = 0.25  # strength_threshold's default
OMEGA = 0.8
MAX_LEVELS = 5
# coarsening stops at max(min_coarse_rows, dense_lu_num_rows) rows
# under the dense LU coarse solver (min_coarse_rows 64, 128 rows)
COARSE_ROWS = 128
# one float32 V-cycle against the float64 reference: f32 rounding
# through the levels reads 6e-8 to 8e-8 here (~1e-6 expected at 128^3),
# bf16 arithmetic 4e-3 to 5e-3 and must fail
VCYCLE_RTOL = 1e-4
# a float64 PCG solve to 1e-10 of ||b||: x within the tolerance times
# the condition number (116 for Poisson at 16^3, 591 for the jump case)
# of the direct solve; it reads 4e-11 to 7e-11
SOLVE_RTOL = 1e-7


def _jump(n, contrast=10.0):
    """7-point finite-volume diffusion on an n^3 grid whose central
    (n/2)^3 cube has ``contrast`` times the coefficient; faces take the
    harmonic mean, so the interface couplings are weak under AHAT."""
    g = np.indices((n, n, n)).reshape(3, -1).T  # (z, y, x) of each row
    inside = np.all((g >= n // 4) & (g < n // 4 + n // 2), axis=1)
    c = np.where(inside, contrast, 1.0)
    idx = np.arange(n ** 3)
    rows, cols, vals = [], [], []
    diag = np.zeros(n ** 3)
    for axis, stride in ((0, n * n), (1, n), (2, 1)):
        for s in (1, -1):
            ok = (g[:, axis] + s >= 0) & (g[:, axis] + s < n)
            i, j = idx[ok], idx[ok] + s * stride
            face = 2.0 * c[i] * c[j] / (c[i] + c[j])
            rows.append(i)
            cols.append(j)
            vals.append(-face)
            diag[i] += face
    # Dirichlet boundary faces
    for axis in range(3):
        diag += c * ((g[:, axis] == 0).astype(float)
                     + (g[:, axis] == n - 1).astype(float))
    rows.append(idx)
    cols.append(idx)
    vals.append(diag)
    A = sps.csr_matrix((np.concatenate(vals),
                        (np.concatenate(rows), np.concatenate(cols))),
                       shape=(n ** 3, n ** 3))
    A.sort_indices()
    return A


def _dirichlet_tail(n):
    """5-point Poisson on an n^2 grid whose last two rows are Dirichlet
    identity rows: the strength graph then ends in empty rows, the edge
    case of the row-segmented maxima (amg/classical.py ``_row_max``)."""
    A = poisson_scipy((n, n)).tolil()
    for i in (n * n - 2, n * n - 1):
        A.rows[i] = [i]
        A.data[i] = [1.0]
    return A.tocsr()


CASES = {
    "poisson10": lambda: poisson_scipy((10, 10, 10)),
    "poisson16": lambda: poisson_scipy((16, 16, 16)),
    "jump12": lambda: _jump(12),
    "poisson48_2d": lambda: poisson_scipy((48, 48)),
    "dirichlet24_2d": lambda: _dirichlet_tail(24),
}
LOCATIONS = ("HOST", "DEVICE")


def _config(location, tolerance=1e-10, extra=""):
    return AMGConfig.from_string(
        '{"config_version": 2, "solver": {"scope": "main",'
        ' "solver": "PCG", "max_iters": 200,'
        f' "tolerance": {tolerance}, "monitor_residual": 1,'
        ' "convergence": "RELATIVE_INI",'
        ' "preconditioner": {"scope": "amg", "solver": "AMG",'
        ' "algorithm": "CLASSICAL", "strength": "AHAT",'
        ' "selector": "PMIS", "interpolator": "D1",'
        ' "smoother": {"scope": "j", "solver": "BLOCK_JACOBI",'
        f' "relaxation_factor": {OMEGA}, "monitor_residual": 0}},'
        ' "presweeps": 1, "postsweeps": 1, "max_iters": 1,'
        f' "min_coarse_rows": 64, "max_levels": {MAX_LEVELS},'
        ' "coarse_solver": "DENSE_LU_SOLVER", "cycle": "V",'
        f' "setup_location": "{location}"{extra},'
        ' "monitor_residual": 0}}}'
    )


@functools.lru_cache(maxsize=None)
def _operator(case):
    return sps.csr_matrix(CASES[case](), dtype=np.float64)


@functools.lru_cache(maxsize=None)
def _program(case, location, dtype="float64", extra=""):
    """The program's PCG-classical solver, set up on the case."""
    A = _operator(case).astype(dtype)
    solver = create_solver(_config(location, extra=extra), "default")
    solver.setup(SparseMatrix.from_scipy(A))
    builders = set(solver.precond.level_builders)
    assert builders == {location.lower()}, builders
    return solver


@functools.lru_cache(maxsize=None)
def _reference(case):
    return ref.hierarchy(_operator(case), THETA,
                         lambda n: host._hash_weights(n, seed=0),
                         MAX_LEVELS, COARSE_ROWS)


def _levels(solver):
    """The program's levels, downloaded: ``[{"A", "P", "R"}]``."""
    out = []
    for lvl in solver.precond.levels:
        d = {"A": lvl.A.to_scipy().tocsr()}
        if lvl.P is not None:
            d.update(P=lvl.P.to_scipy().tocsr(), R=lvl.R.to_scipy().tocsr())
        out.append(d)
    return out


def _program_split(A, location):
    """The program's C/F split of ``A``, by the selector its builder
    runs, with the hash weights of seed 0."""
    max_row_sum = 1.1  # the default: weakens nothing
    if location == "HOST":
        return host.pmis_select(host.strength_ahat(A, THETA, max_row_sum))
    n = A.shape[0]
    rows = np.repeat(np.arange(n, dtype=np.int32), np.diff(A.indptr))
    r, c, v = dev._pad_coo(rows, A.indices.astype(np.int32), A.data,
                           dev._bucket(A.nnz), n)
    r, c, v = jnp.asarray(r), jnp.asarray(c), jnp.asarray(v)
    strong = dev._strength_ahat_dev(r, c, v, n, THETA, max_row_sum)
    lam = jax.ops.segment_sum(strong.astype(jnp.float64),
                              jnp.minimum(c, n - 1), num_segments=n)
    w = lam + jnp.asarray(host._hash_weights(n, seed=0))
    return np.asarray(dev._pmis_dev(r, c, strong, n, w))


def _rel(M, R):
    """Largest entry of M - R over the largest of R."""
    return abs(M - R).max() / abs(R).max()


@pytest.mark.parametrize("location", LOCATIONS)
@pytest.mark.parametrize("case", sorted(CASES))
def test_split_interpolation_and_galerkin(case, location):
    """float64: each level's C/F split equals the reference's, and its P
    and coarse operator agree to 1e-11 of their largest entry."""
    levels = _levels(_program(case, location))
    want = _reference(case)
    assert len(levels) == len(want) >= 3
    for k in range(len(want) - 1):
        np.testing.assert_array_equal(
            _program_split(levels[k]["A"], location), want[k]["cf"])
        P, Pr = levels[k]["P"], want[k]["P"]
        assert P.shape == Pr.shape
        assert _rel(P, Pr) < 1e-11
        assert _rel(levels[k]["R"], Pr.T.tocsr()) < 1e-11
        Ac, Acr = levels[k + 1]["A"], want[k + 1]["A"]
        assert Ac.shape == Acr.shape
        assert _rel(Ac, Acr) < 1e-11


def _program_v_cycle(solver, b):
    amg = solver.precond
    step = jax.jit(amg.make_step())
    x0 = jnp.zeros_like(b)
    return np.asarray(step(amg._collect_params(), b, x0), np.float64)


def _v_cycle_error(solver, levels, seed=0):
    """One V-cycle of the program on a random b, against the reference
    cycle in float64 over ``levels``."""
    n = levels[0]["A"].shape[0]
    b = np.random.default_rng(seed).standard_normal(n)
    dtype = solver.precond.levels[0].A.values.dtype
    got = _program_v_cycle(solver, jnp.asarray(b, dtype))
    want = ref.v_cycle(levels, b, OMEGA)
    return np.linalg.norm(got - want) / np.linalg.norm(want)


@pytest.mark.parametrize("location", LOCATIONS)
@pytest.mark.parametrize("case", sorted(CASES))
def test_float32_v_cycle_matches_reference(case, location):
    """float32: one V-cycle within VCYCLE_RTOL of the reference cycle
    run in float64 over the program's own (downloaded) levels."""
    solver = _program(case, location, "float32")
    assert _v_cycle_error(solver, _levels(solver)) < VCYCLE_RTOL


@pytest.mark.parametrize("case", sorted(CASES))
def test_bfloat16_v_cycle_fails_the_tolerance(case):
    """Planted: the float32 hierarchy cast to bfloat16, every level and
    the cycle's arithmetic, must come out outside the tolerance."""
    solver = _program(case, "HOST", "float32",
                      ', "hierarchy_dtype": "BF16",'
                      ' "level_dtype_policy": "ALL"')
    assert solver.precond.levels[0].A.values.dtype == jnp.bfloat16
    levels = _levels(_program(case, "HOST", "float32"))
    assert _v_cycle_error(solver, levels) > VCYCLE_RTOL


@pytest.mark.parametrize("location", LOCATIONS)
@pytest.mark.parametrize("case", sorted(CASES))
def test_float64_solve_matches_direct_solve(case, location):
    A = _operator(case)
    b = np.random.default_rng(1).standard_normal(A.shape[0])
    res = _program(case, location).solve(jnp.asarray(b))
    assert res.converged
    x = np.asarray(res.x)
    want = spla.spsolve(A.tocsc(), b)
    assert np.linalg.norm(x - want) / np.linalg.norm(want) < SOLVE_RTOL
