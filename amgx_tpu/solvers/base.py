"""Solver framework (reference Solver<TConfig>, solver.h:21-278, solver.cu).

The reference contract — setup / solve_init / solve_iteration /
solve_finalize with a monitored outer loop (solver.cu:586-860) — maps to a
jit-first design:

  * ``setup(A)`` is host-side: builds preconditioner state (inverted
    diagonals, hierarchies, colorings) as pytrees of device arrays.
  * ``solve(b, x0)`` runs ONE fully-jitted function containing the entire
    iteration loop (``lax.while_loop``), residual monitoring, convergence
    and divergence checks, and residual-history recording.  One compile
    per (structure, shape) signature, cached.
  * Solvers used as preconditioners/smoothers expose pure functions:
      - ``make_apply()``  -> fn(params, r) -> z        (zero initial guess)
      - ``make_smooth()`` -> fn(params, b, x, sweeps) -> x
    with all arrays flowing through ``params`` (= ``apply_params()``), so
    outer solvers can embed them in their own jitted loops.

Stationary solvers (Jacobi/GS/DILU/Chebyshev-poly...) implement
``make_step`` and inherit the generic monitored loop; Krylov solvers
override ``make_solve`` wholesale.
"""

from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from amgx_tpu.core import faults
from amgx_tpu.core.matrix import SparseMatrix
from amgx_tpu.core.printing import emit
from amgx_tpu.core.profiling import setup_phase
from amgx_tpu.core.types import NormType

from amgx_tpu.ops.spmv import spmv
from amgx_tpu.ops.norms import norm as _norm, block_norm as _block_norm
from amgx_tpu.solvers.convergence import make_convergence_check


def device_memory_stats():
    """(bytes_in_use, peak_bytes_in_use) from the default device's
    runtime allocator (the TPU HBM counters behind the reference's
    MemoryInfo / "Mem Usage" column, include/memory_info.h:9-33), or
    None when the backend exposes no stats (CPU)."""
    try:
        stats = jax.local_devices()[0].memory_stats()
    except Exception:
        return None
    if not stats:
        return None
    used = stats.get("bytes_in_use")
    peak = stats.get("peak_bytes_in_use", used)
    if used is None:
        return None
    return used, peak


# AMGX_SOLVE_* status codes (reference amgx_c.h:75-80)
SUCCESS = 0
FAILED = 1  # hard failure (NaN/Inf residual)
DIVERGED = 2  # rel_div_tolerance exceeded
NOT_CONVERGED = 3


def donation_enabled() -> bool:
    """Buffer-donation default for jitted solve entry points.  ON for
    accelerator backends — donating x0 lets XLA alias the solution
    output onto it, saving an HBM buffer per solve.  OFF on CPU, where
    donation measurably serializes the otherwise-async XLA dispatch
    (~2ms blocking call vs ~0.3ms, see doc/SERVING.md) and buys
    nothing.  ``AMGX_TPU_DONATE=1/0`` overrides either way."""
    import os

    v = os.environ.get("AMGX_TPU_DONATE")
    if v is not None:
        return v != "0"
    return jax.default_backend() != "cpu"


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SolveResult:
    x: jnp.ndarray
    iters: jnp.ndarray  # i32 scalar
    status: jnp.ndarray  # i32: SUCCESS/FAILED/DIVERGED/NOT_CONVERGED
    final_norm: jnp.ndarray  # (ncomp,) real
    initial_norm: jnp.ndarray  # (ncomp,) real
    history: jnp.ndarray  # (max_iters+1, ncomp) real, NaN-padded

    @property
    def converged(self):
        return self.status == SUCCESS


class Solver:
    """Base solver. Subclasses register via @register_solver(NAME)."""

    registry_name = "?"
    # True if this solver ignores its operator (e.g. NOSOLVER)
    is_identity = False
    # inner steps per reported iteration (s-step solvers override: one
    # SSTEP_PCG outer iteration = s CG steps); telemetry and benches
    # multiply SolveResult.iters by this for cross-solver comparisons
    iterations_scale = 1

    def __init__(self, cfg, scope: str = "default"):
        self.cfg = cfg
        self.scope = scope
        g = lambda k: cfg.get(k, scope)
        self.max_iters = int(g("max_iters"))
        self.tolerance = float(g("tolerance"))
        self.conv_type = str(g("convergence"))
        self.norm_type = NormType(str(g("norm")))
        self.monitor_residual = bool(g("monitor_residual"))
        self.store_res_history = bool(g("store_res_history"))
        self.use_scalar_norm = bool(g("use_scalar_norm"))
        self.relaxation_factor = float(g("relaxation_factor"))
        self.print_solve_stats = bool(g("print_solve_stats"))
        self.obtain_timings = bool(g("obtain_timings"))
        # reference solver.cu:34,541-830: verbosity_level gates all
        # solve/grid printouts (>2 = full tables, 1-2 = summary line,
        # 0 = silent); solver_verbose=1 dumps the solver settings at
        # setup (solver.cu:349)
        self.verbosity = int(g("verbosity_level"))
        self.solver_verbose = bool(g("solver_verbose"))
        # reference convergence_analysis.cu: when > 0, print a
        # convergence-rate analysis over the final N iterations
        self.convergence_analysis = int(g("convergence_analysis"))
        self.rel_div_tolerance = float(g("rel_div_tolerance"))
        self.alt_rel_tolerance = float(g("alt_rel_tolerance"))
        # guardrails (core/errors.py catalog): stagnation detection
        # window and the retry-once-with-safer-config recovery hook
        self.stagnation_window = int(g("stagnation_window"))
        self.solve_retries = int(g("solve_retries"))
        self.solve_retries_used = 0
        self.scaling = str(g("scaling"))
        # overwritten to NONE by make_nested: only the outermost solve()
        # boundary may renumber unknowns
        self.reordering = str(g("matrix_reordering"))
        self._conv_check = make_convergence_check(
            self.conv_type, self.tolerance, self.alt_rel_tolerance
        )
        self.A: Optional[SparseMatrix] = None
        self._params: Any = None
        self._jit_cache: dict = {}
        self.setup_time = 0.0
        # seconds spent restoring a persisted setup (store.load_setup);
        # setup_time stays 0 on a restore — the pair is the
        # skipped-setup assertion surface of tests/test_store.py
        self.restore_time = 0.0
        self.solve_time = 0.0
        # compile-vs-execute split (PR 3): lifetime compile seconds and
        # the compile cost of the LAST solve() call (0 on warm calls)
        self.compile_time = 0.0
        self.last_compile_s = 0.0

    # ------------------------------------------------------------------
    # overridables

    def _setup_impl(self, A: SparseMatrix):
        """Host-side setup; must set self._params (pytree of arrays)."""
        self._params = A

    def make_step(self) -> Callable:
        """Pure fn(params, b, x) -> x : one relaxation sweep."""
        rstep = self.make_residual_step()
        if rstep is None:
            raise NotImplementedError(
                f"{type(self).__name__} provides no stationary step"
            )

        def step(params, b, x):
            A = self.operator_of(params)
            return rstep(params, b, x, b - spmv(A, x))

        return step

    def make_residual_step(self) -> Optional[Callable]:
        """Pure fn(params, b, x, r) -> x consuming the precomputed residual
        r = b - A x.  Solvers that can use it (Jacobi, DILU) return it so
        the monitored loop shares one SpMV per iteration between the step
        and the norm; others return None."""
        return None

    def make_solve(self) -> Callable:
        """Pure fn(params, b, x0) -> SolveResult. Default: monitored
        stationary iteration of make_step (reference solver.cu:795-855)."""
        norm_of = self.make_norm()

        if not self.monitor_residual:
            smooth = self.make_smooth()
            iters = self.max_iters

            def solve_plain(params, b, x0):
                x = smooth(params, b, x0, iters)
                return self._fixed_result(x, b, iters)

            return solve_plain

        rstep = self.make_residual_step()
        if rstep is not None:
            # residual-carrying loop: ONE SpMV per iteration shared between
            # the step and the norm
            def solve_r(params, b, x0):
                A = self.operator_of(params)
                r0 = b - spmv(A, x0)

                def body(c):
                    it, x, (r,), nrm, ini, mx, hist, st = c
                    x = faults.corrupt_nan(
                        "smoother_nan", rstep(params, b, x, r)
                    )
                    r = b - spmv(A, x)
                    nrm = norm_of(r)
                    return self._monitor_update(
                        it + 1, x, (r,), nrm, ini, mx, hist, st
                    )

                return self._monitored_loop(
                    norm_of(r0), body, b, x0, (r0,)
                )

            return solve_r

        step = self.make_step()

        def solve(params, b, x0):
            A = self.operator_of(params)

            def compute_nrm(x):
                return norm_of(b - spmv(A, x))

            def body(c):
                it, x, extra, nrm, ini, mx, hist, st = c
                x = faults.corrupt_nan("smoother_nan", step(params, b, x))
                nrm = compute_nrm(x)
                it = it + 1
                return self._monitor_update(
                    it, x, extra, nrm, ini, mx, hist, st
                )

            return self._monitored_loop(compute_nrm(x0), body, b, x0, ())

        return solve

    def make_apply(self) -> Callable:
        """Pure fn(params, r) -> z, preconditioner application with zero
        initial guess; default = max_iters unmonitored sweeps."""
        smooth = self.make_smooth()
        iters = max(self.max_iters, 1)

        def apply(params, r):
            z = jnp.zeros_like(r)
            return smooth(params, r, z, iters)

        return apply

    # few-sweep loops unroll (cycle smoothers, sweeps 1-4); longer ones use
    # fori_loop to bound trace size
    _UNROLL_LIMIT = 8

    def make_smooth(self) -> Callable:
        """Pure fn(params, b, x, sweeps) -> x (sweeps is static)."""
        step = self.make_step()

        def smooth(params, b, x, sweeps):
            if sweeps <= self._UNROLL_LIMIT:
                for _ in range(sweeps):
                    x = step(params, b, x)
                return faults.corrupt_nan("smoother_nan", x)
            x = jax.lax.fori_loop(
                0, sweeps, lambda i, x: step(params, b, x), x
            )
            return faults.corrupt_nan("smoother_nan", x)

        return smooth

    # ------------------------------------------------------------------
    # shared machinery

    def operator_of(self, params):
        """By convention params is the matrix or a tuple starting with it."""
        return params[0] if isinstance(params, tuple) else params

    @property
    def norm_components(self) -> int:
        if (
            self.A is not None
            and self.A.block_size > 1
            and not self.use_scalar_norm
        ):
            return self.A.block_size
        return 1

    def make_norm(self):
        nt = self.norm_type
        ncomp = self.norm_components
        if ncomp > 1:
            b = self.A.block_size
            return lambda r: _block_norm(r, b, nt)
        return lambda r: jnp.atleast_1d(_norm(r, nt))

    def _monitor_update(
        self, it, x, extra, nrm, nrm_ini, nrm_max, hist, status
    ):
        """Common tail of a monitored loop body: record history, update
        max-norm, derive status."""
        nrm_max = jnp.maximum(nrm_max, nrm)
        hist = hist.at[it].set(nrm)
        done_ok = self._conv_check(nrm, nrm_ini, nrm_max)
        bad = ~jnp.all(jnp.isfinite(nrm))
        status = jnp.where(
            done_ok, jnp.int32(SUCCESS), jnp.int32(NOT_CONVERGED)
        )
        if self.rel_div_tolerance > 0:
            div = jnp.any(nrm > self.rel_div_tolerance * nrm_ini)
            status = jnp.where(div, jnp.int32(DIVERGED), status)
        if self.stagnation_window > 0:
            # stagnation guardrail: the current residual is no better
            # than the BEST of the previous w iterations (min over the
            # window — robust to non-monotone Krylov residuals) —
            # reported as DIVERGED (the nearest reference status) so
            # the solve stops early and the retry hook can act
            w = min(self.stagnation_window, self.max_iters + 1)
            window = jax.lax.dynamic_slice_in_dim(
                hist, jnp.maximum(it - w, 0), w, axis=0
            )
            best = jnp.min(window, axis=0)
            stalled = (it >= w) & jnp.all(nrm >= best)
            status = jnp.where(
                stalled & (status == NOT_CONVERGED),
                jnp.int32(DIVERGED),
                status,
            )
        status = jnp.where(bad, jnp.int32(FAILED), status)
        return (it, x, extra, nrm, nrm_ini, nrm_max, hist, status)

    def _fixed_result(self, x, b, iters) -> SolveResult:
        """Result shell for unmonitored fixed-iteration solves.  Even
        unmonitored solves must never return NaN as SUCCESS (guardrail
        invariant): one cheap all-finite check derives the status."""
        rdt = jnp.real(b).dtype
        ncomp = self.norm_components
        zero = jnp.zeros((ncomp,), rdt)
        status = jnp.where(
            jnp.all(jnp.isfinite(x)),
            jnp.int32(SUCCESS),
            jnp.int32(FAILED),
        )
        return SolveResult(
            x=x,
            iters=jnp.int32(iters),
            status=status,
            final_norm=zero,
            initial_norm=zero,
            history=jnp.full((self.max_iters + 1, ncomp), jnp.nan, rdt),
        )

    def _monitored_loop(self, nrm0, body, b, x0, extra0):
        """Generic monitored while_loop (reference solver.cu:586-860).

        carry = (it, x, extra, nrm, nrm_ini, nrm_max, hist, status); body
        must end with _monitor_update.  ``extra0`` is solver-specific loop
        state (Krylov vectors etc.).
        """
        rdt = jnp.real(b).dtype
        ncomp = self.norm_components
        hist = jnp.full((self.max_iters + 1, ncomp), jnp.nan, rdt)
        hist = hist.at[0].set(nrm0)
        done0 = self._conv_check(nrm0, nrm0, nrm0)
        status0 = jnp.where(
            done0, jnp.int32(SUCCESS), jnp.int32(NOT_CONVERGED)
        )

        def cond(c):
            it, status = c[0], c[7]
            return (status == NOT_CONVERGED) & (it < self.max_iters)

        c0 = (jnp.int32(0), x0, extra0, nrm0, nrm0, nrm0, hist, status0)
        it, x, _, nrm, ini, mx, hist, status = jax.lax.while_loop(
            cond, body, c0
        )
        return SolveResult(
            x=x,
            iters=it,
            status=status,
            final_norm=nrm,
            initial_norm=ini,
            history=hist,
        )

    # ------------------------------------------------------------------
    # public API (reference Solver::setup / solve, solver.cu:333,586)

    def setup(self, A: SparseMatrix):
        t0 = time.perf_counter()
        from amgx_tpu.core import errors as _errors

        if _errors.validation_enabled():
            # typed setup guardrail: NaN/Inf coefficients fail HERE
            # with SetupError, not as a NaN status many layers later
            with setup_phase("validate"):
                _errors.validate_operator(
                    A, where=f"{self.registry_name} setup"
                )
        if self.solver_verbose:
            # reference solver.cu:349: dump the solver settings
            emit(
                f"{self.registry_name} solver settings (scope "
                f"{self.scope!r}): max_iters={self.max_iters} "
                f"tolerance={self.tolerance} norm={self.norm_type.value} "
                f"convergence={self.conv_type} "
                f"relaxation_factor={self.relaxation_factor}"
            )
        self._scale_vecs = None
        self._reorder = None
        if self.scaling.upper() not in ("", "NONE"):
            # scale the system at setup (reference Scaler::setup hook,
            # solver.cu:667-676): work on As = Dr A Dc
            from amgx_tpu.solvers.scalers import create_scaler
            import scipy.sparse as sps

            scaler = create_scaler(self.scaling)
            sp = A.to_scipy()
            r, c = scaler.compute(sp)
            sp = sps.diags_array(r) @ sp @ sps.diags_array(c)
            A = SparseMatrix.from_scipy(
                sp.tocsr().astype(np.dtype(A.values.dtype)),
                block_size=A.block_size,
            )
            self._scale_vecs = (jnp.asarray(r.astype(sp.dtype)),
                                jnp.asarray(c.astype(sp.dtype)))
        reorder_mode = self.reordering
        if reorder_mode.upper() != "NONE":
            # RCM renumbering at the solve boundary (same hook as the
            # scaler): unlocks the windowed gather kernel on TPU
            from amgx_tpu.ops.reorder import maybe_reorder

            A2, perm = maybe_reorder(A, reorder_mode)
            if perm is not None:
                iperm = np.argsort(perm)
                self._reorder = (jnp.asarray(perm), jnp.asarray(iperm))
                A = A2
        self.A = A
        self._setup_impl(A)
        self._jit_cache.clear()
        self.setup_time = time.perf_counter() - t0
        return self

    def resetup(self, A: SparseMatrix):
        """Refresh for a matrix whose VALUES changed but whose structure
        is intact (reference AMGX_solver_resetup / structure_reuse).
        Subclasses take fast paths via ``_resetup_impl``; anything that
        can't falls back to a full setup."""
        if (
            self.A is None
            or self._scale_vecs is not None
            or self._reorder is not None
            or A.n_rows != self.A.n_rows
            or A.nnz != self.A.nnz
            or A.block_size != self.A.block_size
        ):
            return self.setup(A)
        t0 = time.perf_counter()
        if not self._resetup_impl(A):
            return self.setup(A)
        self.A = A
        self._jit_cache.clear()
        self.setup_time = time.perf_counter() - t0
        return self

    def _resetup_impl(self, A: SparseMatrix) -> bool:
        """Attempt a values-only refresh; False -> caller runs setup."""
        return False

    # ------------------------------------------------------------------
    # setup persistence (amgx_tpu.store)

    def _export_setup(self) -> dict:
        """Serializable setup-state tree (leaves limited to what
        :func:`amgx_tpu.store.serialize.flatten` handles).  The base
        shape covers every solver: the set-up operator plus the
        solve-boundary scale/reorder vectors, with a solver-specific
        ``impl`` payload from :meth:`_export_impl`."""
        from amgx_tpu.core.errors import StoreError

        if self.A is None:
            raise StoreError(
                f"{self.registry_name}: save_setup before setup()"
            )
        return {
            "A": self.A,
            "scale": getattr(self, "_scale_vecs", None),
            "reorder": getattr(self, "_reorder", None),
            "impl": self._export_impl(),
        }

    def _import_setup(self, state: dict):
        """Restore from :meth:`_export_setup` WITHOUT re-running the
        expensive setup path.  The default re-derives params from the
        restored operator via ``_setup_impl`` — deterministic and
        cheap for every non-hierarchical solver; AMG overrides it to
        rebuild the level chain from the payload instead of
        re-coarsening."""
        self.A = state["A"]
        self._scale_vecs = state.get("scale")
        self._reorder = state.get("reorder")
        self._import_impl(state.get("impl"))
        self._jit_cache.clear()

    def _export_impl(self):
        """Solver-specific setup state beyond the operator; None when
        params re-derive from A (the default _import_impl path)."""
        return None

    def _import_impl(self, impl):
        self._setup_impl(self.A)

    def save_setup(self, path) -> dict:
        """Persist this solver's completed setup to ``path`` (one
        ``.npz`` payload with embedded JSON manifest) so a later
        process can :meth:`load_setup` it without re-running setup —
        the durable analogue of ``AMGX_write_system`` extended to the
        whole hierarchy.  Returns the manifest."""
        from amgx_tpu.store import serialize

        return serialize.save_setup(self, path)

    @classmethod
    def load_setup(cls, path, cfg=None, expect_dtype=None):
        """Restore a solver persisted with :meth:`save_setup`.

        The payload records the solver class and full config; pass
        ``cfg`` to assert config compatibility instead (content-hash
        mismatch raises :class:`~amgx_tpu.core.errors.StoreError`),
        and ``expect_dtype`` to refuse a payload of another operator
        dtype before anything ships to the device.  The restored
        solver solves with iteration counts identical to the original
        — setup (for AMG: coarsening + Galerkin) is skipped, not
        re-run (``setup_time`` stays 0; ``restore_time`` holds the
        import cost)."""
        from amgx_tpu.store import serialize

        return serialize.load_setup(
            path, cfg=cfg, expect_dtype=expect_dtype
        )

    def reductions_per_iteration(self):
        """Global reductions (dots + norms — the cross-chip ``psum``
        sync points of a sharded solve) one monitored iteration of
        this solver's compiled loop body executes, counted by tracing
        the iteration protocol under
        :func:`amgx_tpu.ops.blas.reduction_counter`.  ``None`` when
        the solver exposes no step/iterate protocol (GMRES/IDR
        override ``make_solve`` wholesale).  Cached per setup (the
        ``_jit_cache`` clears on setup/resetup); the number behind the
        ``amgx_solver_reductions_total`` telemetry family and the
        ci/smoother_bench.py reductions-per-s-steps gate."""
        key = "__reductions_per_iteration__"
        if key in self._jit_cache:
            return self._jit_cache[key]
        try:
            val = self._count_iteration_reductions()
        except Exception:  # noqa: BLE001 — accounting must never fail
            val = None
        self._jit_cache[key] = val
        return val

    def cycle_passes_per_iteration(self):
        """Fine-grid operator passes one iteration of this solver
        executes (trace-time count under
        :data:`amgx_tpu.ops.spmv.op_pass_counter`).  ``None`` for
        solvers without a cycle notion — the AMG hierarchy overrides
        this; the number feeds ``amgx_solver_cycle_passes_total``."""
        return None

    def _count_iteration_reductions(self):
        """Trace one monitored-loop body (iterate + residual-norm
        monitor) and count the reduction sites."""
        from amgx_tpu.ops import blas

        if self.A is None:
            return None
        params = self.apply_params()
        spec = jax.ShapeDtypeStruct(
            (self.A.n_rows * self.A.block_size,),
            jnp.zeros((), self.A.values.dtype).dtype,
        )
        norm_of = self.make_norm() if self.monitor_residual else None

        if hasattr(self, "_make_init"):
            try:
                init_fn, iter_fn = self._make_init(), self._make_iter()
            except NotImplementedError:
                init_fn = None
            if init_fn is not None:
                extra = jax.eval_shape(init_fn, params, spec, spec)

                def body(p, b, x, e):
                    x, e = iter_fn(p, b, x, e)
                    return norm_of(e[0]) if norm_of is not None else x

                with blas.reduction_counter() as c:
                    jax.eval_shape(body, params, spec, spec, extra)
                return c.count

        rstep = self.make_residual_step()
        if rstep is not None:
            def body_r(p, b, x, r):
                x = rstep(p, b, x, r)
                r = b - spmv(self.operator_of(p), x)
                return norm_of(r) if norm_of is not None else x

            with blas.reduction_counter() as c:
                jax.eval_shape(body_r, params, spec, spec, spec)
            return c.count

        step = self.make_step()

        def body_s(p, b, x):
            x = step(p, b, x)
            if norm_of is not None:
                return norm_of(b - spmv(self.operator_of(p), x))
            return x

        with blas.reduction_counter() as c:
            jax.eval_shape(body_s, params, spec, spec)
        return c.count

    def make_batch_params(self):
        """Traced values-only params rebuild, for batched group solves
        (:mod:`amgx_tpu.serve`).

        Returns ``(template, fn)`` where ``fn(template, values) ->
        params`` is a pure jit/vmap-safe function rebuilding this
        solver's ``apply_params()`` pytree for a coefficient set
        ``values`` on the SAME sparsity pattern as the setup matrix —
        the traced analogue of :meth:`resetup`.  ``template`` is a
        pytree of device arrays holding everything pattern-specific
        (index structures, transfer operators, SpGEMM plans); it is
        passed to ``fn`` as an ARGUMENT so the serve layer can hand it
        to one jit-compiled program per shape bucket instead of baking
        the pattern into the compiled code.

        Returns None when the solver has no traced values-only rebuild
        (callers fall back to sequential resetup + solve).  The default
        covers solvers whose params ARE the matrix.
        """
        if self._params is None or self._params is not self.A:
            return None
        return self.A, lambda t, v: t.replace_values(v)

    def apply_params(self):
        return self._params

    def collect_setup_profile(self) -> dict:
        """Merged setup-phase profile (``AMGSolver.setup_profile``
        keys: strength/cf_split/aggregation/interp/rap_plan/
        rap_execute/transfer/finalize/... ) of this solver and any
        nested preconditioner — the dict behind ``obtain_timings``'s
        ``setup:<phase>`` lines."""
        prof = dict(getattr(self, "setup_profile", None) or {})
        inner = getattr(self, "precond", None)
        if inner is not None and inner is not self:
            for k, v in inner.collect_setup_profile().items():
                prof[k] = prof[k] + v if k in prof else v
        return prof

    def solve(self, b, x0=None, zero_initial_guess=False,
              block=True) -> SolveResult:
        """Monitored solve.  ``block=False`` is the async mode (PR 3):
        the call returns right after the device dispatch with a
        SolveResult backed by on-device arrays — status / iterations /
        history materialize lazily when first read — and performs no
        host sync of its own.  Sync-requiring features (solve stats
        printing, obtain_timings, convergence analysis; a triggered
        retry) still synchronize even with ``block=False``.

        Buffer donation: when this call OWNS the initial-guess buffer
        (x0 omitted, zero_initial_guess, a host array, or a
        scaled/reordered copy), the jitted solve donates it
        (``donate_argnums``) so XLA writes the solution in place.  A
        caller-owned device x0 is never donated — that aliasing caveat
        is the one documented in doc/SERVING.md.

        The call is a host span ``solve`` (``solver`` metadata) on the
        profiler's clock, with a ``solve:dispatch`` span around the
        jitted call; nothing is added inside the traced program."""
        with jax.profiler.TraceAnnotation(
            "solve", solver=self.registry_name
        ):
            return self._solve(b, x0, zero_initial_guess, block)

    def _solve(self, b, x0, zero_initial_guess, block) -> SolveResult:
        if self.A is None:
            raise RuntimeError("solve() before setup()")
        b = jnp.asarray(b)
        donate = (
            x0 is None
            or zero_initial_guess
            or not isinstance(x0, jax.Array)
        ) and donation_enabled()
        if x0 is None or zero_initial_guess:
            x0 = jnp.zeros_like(b)
        else:
            x0 = jnp.asarray(x0)
        if self._scale_vecs is not None:
            r_s, c_s = self._scale_vecs
            b = r_s * b
            x0 = x0 / jnp.where(c_s != 0, c_s, 1.0)
            # the scaled x0 is a fresh array we own
            donate = donation_enabled()
        if self._reorder is not None:
            perm, _ = self._reorder
            b = b[perm]
            x0 = x0[perm]
            donate = donation_enabled()  # likewise the permuted copy
        key = (b.shape, b.dtype.name, x0.dtype.name, donate)
        fn = self._jit_cache.get(key)
        if fn is None:
            fn = self._compile_solve(key, b, x0, donate)
        else:
            self.last_compile_s = 0.0
        t0 = time.perf_counter()
        self.solve_retries_used = 0
        with jax.profiler.TraceAnnotation("solve:dispatch"):
            res = fn(self.apply_params(), b, x0)
        if self.solve_retries > 0:
            res = self._retry_if_failed(res, key, b)
        if self._reorder is not None:
            res = dataclasses.replace(res, x=res.x[self._reorder[1]])
        if self._scale_vecs is not None:
            res = dataclasses.replace(res, x=self._scale_vecs[1] * res.x)
        # async mode skips the device sync unless a reporting feature
        # needs concrete numbers anyway
        if (
            block
            or self.print_solve_stats
            or self.obtain_timings
            or self.convergence_analysis > 0
        ):
            res.x.block_until_ready()
        self.solve_time = time.perf_counter() - t0
        if self.print_solve_stats and self.verbosity > 2:
            self._print_stats(res)
        elif self.print_solve_stats and self.verbosity in (1, 2):
            # reduced one-line summary (reference solver.cu:760,830)
            emit(
                f"         Total Iterations: {int(res.iters)}  "
                f"status: {int(res.status)}"
            )
        if self.convergence_analysis > 0 and res.history is not None:
            self._print_convergence_analysis(res)
        if self.obtain_timings:
            # compile reported SEPARATELY from solve: the first call's
            # jit tracing/compilation is a one-off cost and folding it
            # into solve seconds misstates per-iteration cost (warm
            # calls report compile: 0)
            emit(
                f"Total Time: {self.setup_time + self.last_compile_s + self.solve_time:10.6f}\n"
                f"    setup: {self.setup_time:10.6f} s\n"
                f"    compile: {self.last_compile_s:10.6f} s\n"
                f"    solve: {self.solve_time:10.6f} s\n"
                f"    solve(per iteration): "
                f"{self.solve_time / max(1, int(res.iters)):10.6f} s"
            )
            setup_prof = self.collect_setup_profile()
            if setup_prof:
                # setup-phase anatomy (PR 5): the cold-setup cost
                # broken down the way compile:/solve: split the solve
                # side — doc/PERFORMANCE.md "Setup-phase anatomy"
                lines = []
                for k in sorted(setup_prof):
                    v = setup_prof[k]
                    if isinstance(v, float):
                        lines.append(f"    setup:{k}: {v:10.6f} s")
                    else:
                        lines.append(f"    setup:{k}: {v}")
                emit("\n".join(lines))
            mem = device_memory_stats()
            if mem is not None:
                # reference "Mem Usage" column (memory_info.h:9-33);
                # on TPU this is live/peak HBM from the runtime
                emit(
                    f"    Mem Usage: {mem[0] / 2**30:10.4f} GB in use, "
                    f"peak {mem[1] / 2**30:10.4f} GB"
                )
            # re-emit the same timing lines through the telemetry
            # registry (amgx_solver_* / amgx_setup_phase_* metrics) and
            # drop a flight record for the direct-API solve — this
            # branch already synchronized, so reading iters/status
            # costs nothing extra.  Telemetry must never fail a solve.
            self._telemetry_observe(res, setup_prof)
        return res

    def _telemetry_observe(self, res: SolveResult, setup_prof: dict):
        """Fold one timed solve into the process telemetry registry
        (obtain_timings re-emission) and the default flight-record
        path (``path="direct"``).  Best-effort: any failure —
        including the ``telemetry_export`` injected fault — is
        swallowed; the solve result is already computed."""
        try:
            from amgx_tpu import telemetry

            if not telemetry.telemetry_enabled():
                return
            reg = telemetry.get_registry()
            # iterations are reported in INNER-step equivalents
            # (iterations_scale: one s-step outer = s CG steps) so
            # histograms compare across solver families; reductions
            # multiply the per-loop-body count by loop-body
            # executions (= SolveResult.iters), making the
            # communication win observable: reductions/iterations
            # ~ 3 for classic monitored PCG, ~ 2/s for SSTEP_PCG
            red = self.reductions_per_iteration()
            cp = self.cycle_passes_per_iteration()
            reg.record_solver(
                self.registry_name,
                setup_s=self.setup_time,
                compile_s=self.last_compile_s,
                solve_s=self.solve_time,
                iterations=int(res.iters) * int(self.iterations_scale),
                reductions=(red or 0) * int(res.iters),
                cycle_passes=(cp or 0) * int(res.iters),
                setup_phases={
                    k: v for k, v in (setup_prof or {}).items()
                    if isinstance(v, float)
                },
            )
            from amgx_tpu.telemetry.registry import default_recorder

            default_recorder().record(
                fingerprint=(
                    self.A.fingerprint() if self.A is not None else ""
                ),
                config=self.cfg.content_hash(),
                lane="direct",
                tenant="-",
                iterations=int(res.iters),
                final_residual=float(np.max(np.asarray(res.final_norm))),
                status=int(res.status),
                stages={
                    "setup": self.setup_time,
                    "compile": self.last_compile_s,
                    "solve": self.solve_time,
                },
                path="direct",
            )
        except Exception:
            # observability is free to fail; the solve is not —
            # but KeyboardInterrupt/SystemExit must still propagate
            pass

    def _compile_solve(self, key, b, x0, donate):
        """AOT-compile the jitted solve for this signature, timing the
        compile separately from execution (``last_compile_s`` /
        ``compile_time``); falls back to the tracing jit wrapper when
        AOT rejects the params pytree."""
        t0 = time.perf_counter()
        jitted = jax.jit(
            self.make_solve(),
            donate_argnums=(2,) if donate else (),
        )
        try:
            fn = jitted.lower(self.apply_params(), b, x0).compile()
        except Exception:
            fn = jitted
        self._jit_cache[key] = fn
        self.last_compile_s = time.perf_counter() - t0
        self.compile_time += self.last_compile_s
        return fn

    # result-status preference order for the retry hook: a retry's
    # outcome replaces the original only when strictly better
    _STATUS_RANK = {FAILED: 0, DIVERGED: 1, NOT_CONVERGED: 2, SUCCESS: 3}

    def _retry_if_failed(self, res: SolveResult, key, b) -> SolveResult:
        """Retry-with-safer-config recovery hook (``solve_retries``).

        A FAILED/DIVERGED solve retries up to ``solve_retries`` times,
        each attempt evicting the possibly-defective MAIN executable (a
        fresh trace escapes spent fault injections and any trace-level
        corruption) and restarting from a zero initial guess.  The
        first retry keeps the configuration — it targets transient/
        trace corruption; further retries halve the relaxation factor
        each time (under-relaxation is the classic safer setting for
        stationary/smoothed iterations) — they target genuine
        divergence.  Retry executables are cached under their own
        (key, attempt) slot: the first failing solve traces them fresh
        (that's the corruption escape), repeated failing solves reuse
        the clean trace instead of paying a recompile per retry.  The
        best result by status wins; healthy solves pay only one scalar
        status sync."""
        attempt = 0
        while (
            attempt < self.solve_retries
            and int(res.status) in (FAILED, DIVERGED)
        ):
            attempt += 1
            self.solve_retries_used = attempt
            self._jit_cache.pop(key, None)
            rkey = ("retry", key, attempt)
            fn = self._jit_cache.get(rkey)
            if fn is None:
                old_omega = self.relaxation_factor
                self.relaxation_factor = old_omega * 0.5 ** (attempt - 1)
                try:
                    fn = jax.jit(self.make_solve())
                finally:
                    self.relaxation_factor = old_omega
                self._jit_cache[rkey] = fn
            retry = fn(self.apply_params(), b, jnp.zeros_like(b))
            if self._STATUS_RANK.get(int(retry.status), 0) > \
                    self._STATUS_RANK.get(int(res.status), 0):
                res = retry
        return res

    def _print_stats(self, res: SolveResult):
        """Residual table in the reference output format (README.md:118-131)."""
        import numpy as np

        hist = np.asarray(res.history)
        iters = int(res.iters)
        lines = ["           iter      residual           rate",
                 "         --------------------------------------"]
        for i in range(min(iters, self.max_iters) + 1):
            row = hist[i]
            if np.all(np.isnan(row)):
                continue
            r = float(np.max(row))
            if i == 0:
                lines.append(f"            Ini {r:18.6e}")
            else:
                prev = float(np.max(hist[i - 1]))
                rate = r / prev if prev > 0 else 0.0
                lines.append(f"            {i:3d} {r:18.6e} {rate:14.4f}")
        st = int(res.status)
        label = {
            SUCCESS: "success",
            FAILED: "failed (nan/inf)",
            DIVERGED: "diverged",
            NOT_CONVERGED: "not converged",
        }.get(st, f"unknown ({st})")
        lines.append("         --------------------------------------")
        emit("\n".join(lines))
        emit(
            f"         Total Iterations: {iters}\n"
            f"         Avg Convergence Rate: "
            f"{self._avg_rate(hist, iters):18.4f}\n"
            f"         Final Residual: {float(np.max(hist[iters])):18.6e}\n"
            f"         Residual reduction: "
            f"{float(np.max(hist[iters]) / max(np.max(hist[0]), 1e-300)):18.6e}\n"
            f"         Solve status: {label}"
        )

    def _print_convergence_analysis(self, res: SolveResult):
        """Reference convergence_analysis.cu: geometric-mean rate and
        per-iteration rates over the last ``convergence_analysis``
        iterations."""
        import numpy as np

        hist = np.asarray(res.history)
        iters = int(res.iters)
        k = min(self.convergence_analysis, iters)
        if k < 1:
            return
        rows = []
        for i in range(iters - k + 1, iters + 1):
            prev = float(np.max(hist[i - 1]))
            cur = float(np.max(hist[i]))
            rows.append(
                f"           iter {i:3d}: rate "
                f"{(cur / prev if prev > 0 else 0.0):10.4f}"
            )
        r0 = float(np.max(hist[iters - k]))
        rn = float(np.max(hist[iters]))
        geo = (rn / r0) ** (1.0 / k) if r0 > 0 else 0.0
        emit(
            "         Convergence analysis (last %d iterations):\n" % k
            + "\n".join(rows)
            + f"\n           geometric-mean rate: {geo:10.4f}"
        )

    @staticmethod
    def _avg_rate(hist, iters):
        import numpy as np

        if iters < 1:
            return 0.0
        r0, rn = np.max(hist[0]), np.max(hist[iters])
        if r0 <= 0:
            return 0.0
        return float((rn / r0) ** (1.0 / iters))


class IdentitySolverMixin:
    """For NOSOLVER-style solvers: apply is identity, smooth is no-op."""

    is_identity = True
