"""Dense LU coarse solver (reference dense_lu_solver.cu: cuSOLVER
getrf/getrs on the densified coarse matrix).

TPU form: densify at setup (host), LU-factorize once with
``jax.scipy.linalg.lu_factor`` (batched MXU-friendly), apply is a pair of
triangular solves inside the jitted cycle.  Size guards
dense_lu_num_rows/dense_lu_max_rows live in the AMG driver (amg.cu:76-85).

Zero-pivot guardrail: ``jax.scipy.linalg.lu_factor`` does not signal
singularity — a zero pivot silently propagates NaN into every coarse
correction (and from there into the whole V-cycle).  Setup therefore
checks the U diagonal on host; per ``dense_lu_zero_pivot`` policy a
singular factorization either raises :class:`SingularDiagonalError`
(RAISE) or switches the coarse solve to the pseudoinverse
(REGULARIZE): the correction becomes the least-squares solution,
exact on the range of the coarse operator and zero on its null space
— a degraded-but-convergent coarse solve, justified by inexact-
coarse-solver analysis (the outer iteration absorbs a bounded
coarse-solve perturbation, unlike a ridge whose 1/delta null-space
response would blow the cycle up)."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from amgx_tpu.core import faults
from amgx_tpu.core.errors import SingularDiagonalError
from amgx_tpu.solvers.base import Solver
from amgx_tpu.solvers.registry import register_solver


def _bad_pivots(lu) -> bool:
    """Host check of the factorization's U diagonal: exact zeros, NaNs
    (LAPACK writes NaN past a breakdown), or pivots tiny enough that
    back-substitution amplifies into overflow."""
    d = np.abs(np.diag(np.asarray(lu)))
    if d.size == 0:
        return False
    if not np.all(np.isfinite(np.asarray(lu))):
        return True
    dmax = float(d.max())
    if dmax == 0.0:
        return True
    tiny = np.finfo(d.dtype).eps * d.shape[0] * dmax
    return bool(np.any(d <= tiny))


@register_solver("DENSE_LU_SOLVER")
class DenseLUSolver(Solver):
    def __init__(self, cfg, scope="default"):
        super().__init__(cfg, scope)
        self.zero_pivot_policy = str(
            cfg.get("dense_lu_zero_pivot", scope)
        ).upper()
        self._pinv_mode = False

    def _setup_impl(self, A):
        dense = np.asarray(A.to_dense())
        if faults.should_fire("coarse_lu_zero_pivot"):
            # injected singularity: zero the last row/column so the
            # factorization hits an exact zero pivot deterministically
            dense = dense.copy()
            dense[-1, :] = 0.0
            dense[:, -1] = 0.0
        if dense.dtype.itemsize < 4:
            # sub-f32 hierarchies (hierarchy_dtype=BFLOAT16): LAPACK
            # has no bf16/f16 factorization — factor in f32; the cycle
            # casts the correction back to the level dtype
            dense = dense.astype(np.float32)
        self._pinv_mode = False
        lu, piv = jax.scipy.linalg.lu_factor(jnp.asarray(dense))
        if _bad_pivots(lu):
            if self.zero_pivot_policy == "RAISE":
                raise SingularDiagonalError(
                    f"DENSE_LU: singular coarse matrix "
                    f"({A.n_rows} rows): zero/tiny pivot in LU"
                )
            # REGULARIZE: least-squares coarse solve via the
            # pseudoinverse (exact on the range, zero on the null
            # space); the apply becomes one dense matvec
            import warnings

            warnings.warn(
                f"DENSE_LU: singular coarse matrix ({A.n_rows} rows); "
                "switching to pseudoinverse coarse solve "
                "(dense_lu_zero_pivot=REGULARIZE)"
            )
            self._pinv_mode = True
            pinv = np.linalg.pinv(dense)
            if not np.all(np.isfinite(pinv)):
                raise SingularDiagonalError(
                    f"DENSE_LU: pseudoinverse of the coarse matrix "
                    f"({A.n_rows} rows) is non-finite"
                )
            self._params = (A, jnp.asarray(pinv), piv)
            return
        self._params = (A, lu, piv)

    # ------------------------------------------------------------------
    # setup persistence (amgx_tpu.store): the factors ARE this solver's
    # setup — persisting them makes restore skip the O(n^3)
    # refactorization (and makes the dense-factor store bytes the
    # coarse_solver=INEXACT comparison measures explicit).

    def _export_impl(self):
        _, fac, piv = self._params
        return {"fac": fac, "piv": piv, "pinv": bool(self._pinv_mode)}

    def _import_impl(self, impl):
        if not impl or impl.get("fac") is None:
            return self._setup_impl(self.A)
        self._pinv_mode = bool(impl.get("pinv"))
        self._params = (self.A, impl["fac"], impl["piv"])

    def make_batch_params(self):
        if self._pinv_mode:
            # the traced rebuild refactorizes with plain LU, which is
            # exactly what just failed — no batch fast path
            return None
        A0 = self._params[0]
        if A0.block_size != 1:
            return None

        def fn(t, v):
            A = t.replace_values(v)
            if A.has_dense:
                dense = A.dense
            else:
                dense = (
                    jnp.zeros((A.n_rows, A.n_cols), A.values.dtype)
                    .at[A.row_ids, A.col_indices]
                    .add(A.values)
                )
            if dense.dtype.itemsize < 4:
                # same sub-f32 upcast as _setup_impl (no bf16 LAPACK)
                dense = dense.astype(jnp.float32)
            lu, piv = jax.scipy.linalg.lu_factor(dense)
            return A, lu, piv

        return A0, fn

    def make_apply(self):
        if self._pinv_mode:

            def apply_pinv(params, r):
                _, pinv, _ = params
                return pinv @ r

            return apply_pinv

        def apply(params, r):
            _, lu, piv = params
            # a sub-f32 level's factors are f32 (_setup_impl): solve
            # in f32, the cycle casts the correction back
            return jax.scipy.linalg.lu_solve((lu, piv), r.astype(lu.dtype))

        return apply

    def make_smooth(self):
        apply = self.make_apply()

        def smooth(params, b, x, sweeps):
            # direct solve: the result does not depend on x or sweeps
            return apply(params, b)

        return smooth

    def make_solve(self):
        apply = self.make_apply()

        def solve(params, b, x0):
            x = apply(params, b)
            return self._fixed_result(x, b, 1)

        return solve


@register_solver("DENSE_LU")
class DenseLUAlias(DenseLUSolver):
    pass
