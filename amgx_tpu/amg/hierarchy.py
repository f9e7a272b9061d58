"""AMG driver: hierarchy setup loop, cycles, and the registered "AMG"
solver (reference src/amg.cu setup loop :201-418, AMG_Level amg_level.h,
cycles src/cycles/).

TPU design: setup is host-side (scipy coarsening per level — shapes are
data-dependent), producing a list of levels with static shapes; the solve
path builds ONE jitted cycle function by Python recursion over the static
level list, so a V-cycle with nested smoothers, restriction, prolongation
and the dense coarse solve is a single XLA program.  Hierarchy rebuild =
retrace; value-only updates reuse structure (reference
structure_reuse_levels / replace_coefficients).

Cycles: V, W, F and CG/CGF K-cycles (reference cycles/).  Branching
cycles (W/F/K) are truncated below _W_MAX_BRANCH_LEVELS to bound the
unrolled program size.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from amgx_tpu.core.matrix import SparseMatrix
from amgx_tpu.core.profiling import (
    named_scope,
    setup_level,
    setup_phase,
    setup_profile_scope,
    setup_transfer,
)
from amgx_tpu.ops.blas import dot
from amgx_tpu.ops.spmv import op_pass_counter, spmv
from amgx_tpu.ops.stencil import fused_cycle_leg
from amgx_tpu.solvers.base import Solver
from amgx_tpu.solvers.registry import (
    SolverRegistry,
    make_nested,
    register_solver,
)


# gamma-cycle branch-depth cap shared by the serial and distributed
# cycles
W_MAX_BRANCH_LEVELS = 6

# hierarchy_dtype spellings -> numpy dtype (SAME = keep the input
# dtype; bf16 resolves through jax's ml_dtypes registration)
_HIERARCHY_DTYPES = {
    "FLOAT64": np.float64, "F64": np.float64, "DOUBLE": np.float64,
    "FLOAT32": np.float32, "F32": np.float32, "FLOAT": np.float32,
    "BFLOAT16": "bfloat16", "BF16": "bfloat16",
}


def _to_dtype(v, dt):
    """Trace-level cast helper: no-op when already at ``dt`` (keeps
    all-one-dtype cycles byte-identical to the pre-policy program)."""
    return v if v.dtype == dt else v.astype(dt)


def levels_bitwise_equal(amg_a, amg_b) -> str | None:
    """Compare two set-up AMG hierarchies for BITWISE equality of
    their level structure — level count, A/P/R presence, patterns,
    values, and rebuilt acceleration structures (DIA/ELL values).

    Returns None when equal, else a short human-readable mismatch
    description.  This is the hierarchy comparison for store
    round-trips: a hierarchy restored from a saved set-up must equal
    the one that was saved."""
    if len(amg_a.levels) != len(amg_b.levels):
        return (
            f"level count {len(amg_a.levels)} != {len(amg_b.levels)}"
        )
    for la, lb in zip(amg_a.levels, amg_b.levels):
        for field in ("A", "P", "R"):
            ma, mb = getattr(la, field), getattr(lb, field)
            if (ma is None) != (mb is None):
                return (
                    f"level {la.level_id} {field} presence mismatch"
                )
            if ma is None:
                continue
            for arr in ("row_offsets", "col_indices", "values"):
                if not np.array_equal(
                    np.asarray(getattr(ma, arr)),
                    np.asarray(getattr(mb, arr)),
                ):
                    return (
                        f"level {la.level_id} {field}.{arr} not "
                        "bitwise-identical"
                    )
            if ma.geo_meta != mb.geo_meta:
                return f"level {la.level_id} {field}.geo_meta mismatch"
            for accel in ("dia_vals", "ell_vals", "mf_coefs", "geo_w"):
                va, vb = getattr(ma, accel), getattr(mb, accel)
                if (va is None) != (vb is None):
                    return (
                        f"level {la.level_id} {field}.{accel} "
                        "presence mismatch"
                    )
                if va is not None and not np.array_equal(
                    np.asarray(va), np.asarray(vb)
                ):
                    return (
                        f"level {la.level_id} {field}.{accel} not "
                        "bitwise-identical"
                    )
    return None


class AMGLevel:
    """One hierarchy level (reference AMG_Level, amg_level.h:50)."""

    def __init__(self, A: SparseMatrix, level_id: int):
        self.A = A
        self.level_id = level_id
        self.P: SparseMatrix | None = None
        self.R: SparseMatrix | None = None
        self.smoother: Solver | None = None
        # device numeric-Galerkin plan to the NEXT level (structure
        # reuse; amg/spgemm.py); None when the pattern can't be planned
        # (e.g. truncated interpolation drops product entries)
        self.rap_plan = None

    @property
    def n_rows(self):
        return self.A.n_rows

    @property
    def nnz(self):
        return self.A.nnz


@register_solver("AMG")
class AMGSolver(Solver):
    """Algebraic multigrid as a Solver (reference
    algebraic_multigrid_solver.cu + AMG<> driver amg.cu)."""

    def __init__(self, cfg, scope="default"):
        super().__init__(cfg, scope)
        g = lambda k: cfg.get(k, scope)
        self.algorithm = str(g("algorithm")).upper()
        self.cycle_type = str(g("cycle")).upper()
        self.max_levels = int(g("max_levels"))
        self.min_coarse_rows = int(g("min_coarse_rows"))
        self.min_fine_rows = int(g("min_fine_rows"))
        self.presweeps = int(g("presweeps"))
        self.postsweeps = int(g("postsweeps"))
        self.finest_sweeps = int(g("finest_sweeps"))
        self.coarsest_sweeps = int(g("coarsest_sweeps"))
        self.cycle_iters = int(g("cycle_iters"))
        self.dense_lu_num_rows = int(g("dense_lu_num_rows"))
        self.dense_lu_max_rows = int(g("dense_lu_max_rows"))
        self.print_grid_stats = bool(g("print_grid_stats"))
        self.intensive_smoothing = bool(g("intensive_smoothing"))
        # coarse-level locality renumbering: internal to the hierarchy
        # (folded into P/R), but matrix_reordering=NONE opts out so
        # reference level orderings stay reproducible.  Read from config,
        # not self.reordering — make_nested neutralizes only the
        # solve-boundary permutation.
        self.coarse_reorder = str(g("matrix_reordering")).upper()
        # reference amg.cu:365: coarsening continues only while
        # nc <= coarsen_threshold * n (guards coarsening stalls where
        # the grid shrinks too slowly to be worth another level)
        self.coarsen_threshold = float(g("coarsen_threshold"))
        # scaled error correction (reference
        # aggregation_amg_level.cu:696-805): 2/4 = lambda minimizing
        # ||r - lambda*A e|| (= <r,Ae>/<Ae,Ae>), 3/5 = energy lambda
        # <r,e>/<e,Ae>; >3 additionally smooths e (Vanek).  The scale
        # recomputes every cycle — the dots fuse into the XLA program
        # (reuse_scale is therefore N/A on TPU, config/params.py).
        # AGGREGATION levels only, like the reference (the classical
        # level has no scaled-correction path).
        self.error_scaling = (
            int(g("error_scaling"))
            if self.algorithm == "AGGREGATION" else 0
        )
        self.scaling_smoother_steps = int(g("scaling_smoother_steps"))
        # structure_reuse_levels (reference amg_config): 0 = resetup
        # rebuilds everything; k > 0 = the top k Galerkin products
        # re-evaluate on device (amg/spgemm.py plans); < 0 = all levels
        self.structure_reuse = int(g("structure_reuse_levels"))
        # MATRIX_FREE stencil operators (ops/stencil.py): detect
        # verified constant/axis-separable stencils at setup and store
        # compact coefficient state instead of O(nnz) DIA planes; the
        # fused_cycle knob additionally collapses each descent leg
        # (smooth -> residual -> restrict) on matrix-free levels into
        # one fine-grid pass.  fused_cycle=0 is the reference path the
        # parity gates diff against.
        self.matrix_free = bool(g("matrix_free"))
        self.fused_cycle = bool(g("fused_cycle"))
        # per-level precision policy (the cheap-preconditioner mode,
        # ROADMAP item 3 / SParSH-AMG): hierarchy values cast to
        # hierarchy_dtype at _finalize_setup — COARSE casts levels >= 1
        # plus every P/R, ALL also the finest — riding the batched
        # _upload_levels transfer, so cast bytes never ship twice
        self.hierarchy_dtype = str(g("hierarchy_dtype")).upper()
        self.level_dtype_policy = str(g("level_dtype_policy")).upper()
        if self.intensive_smoothing:
            self.presweeps = max(self.presweeps, 4)
            self.postsweeps = max(self.postsweeps, 4)
            self.coarsest_sweeps = max(self.coarsest_sweeps, 8)
        self.levels: list[AMGLevel] = []
        self.coarse_solver: Solver | None = None
        # setup-phase profile (core/profiling.py): inclusive seconds
        # per phase, the self-time totals host_s / device_s /
        # transfer_s / compile_s / unattributed_s, and counters
        # (syncs, transfer_batches, transfer_arrays)
        self.setup_profile: dict = {}
        # classical setup: which builder ("device" / "host") built each
        # coarse level, in level order — the device pipeline's only
        # route to the host is DeviceSetupOverflow, and this says
        # where that happened
        self.level_builders: list = []
        # setup-phase counters (amgx_tpu.store assertion surface):
        # coarsen_calls/levels_built count the expensive hierarchy
        # construction; a store restore leaves both at 0 and flips
        # restored — "restore skips setup" is checkable, not vibes
        self.setup_stats: dict = {
            "coarsen_calls": 0,
            "levels_built": 0,
            "restored": False,
        }

    # ------------------------------------------------------------------
    # setup (reference AMG_Setup::setup, amg.cu:147-418)

    def _build_coarse(self, Asp, level_id: int):
        """``(P, R, Ac, geo_info)`` of the next level: ``geo_info`` is
        the ``(grid, block)`` of a geometric aggregation, else None."""
        if self.algorithm == "AGGREGATION":
            from amgx_tpu.amg.aggregation import build_aggregation_level

            return build_aggregation_level(Asp, self.cfg, self.scope)
        if self.algorithm == "ENERGYMIN":
            from amgx_tpu.amg.energymin import build_energymin_level

            return (*build_energymin_level(Asp, self.cfg, self.scope),
                    None)
        # device-resident classical pipeline (VERDICT r4 #1): strength,
        # PMIS, D1/D2/MULTIPASS and the Galerkin RAP run as XLA
        # programs with scalar-only host syncs; non-covered configs use
        # the host path.  AUTO is backend-aware: on an accelerator the
        # pipeline keeps setup off the host (on a TPU v5e at 128^3 it
        # took 167.6 s against the host path's 42.2 s: PERF.md, Open
        # questions); on the CPU backend "device" is the same core the
        # scipy path uses, minus nothing, plus per-level XLA compiles —
        # scipy wins there (26 s vs 168 s at 96^3, ci/setup_profile.py)
        loc = str(self.cfg.get("setup_location", self.scope)).upper()
        explicit_device = loc == "DEVICE"
        if loc == "AUTO":
            import jax

            loc = (
                "DEVICE" if jax.default_backend() != "cpu" else "HOST"
            )
        if loc != "HOST":
            from amgx_tpu.amg.device_setup import (
                build_classical_level_device,
                device_setup_eligible,
            )

            if device_setup_eligible(self.cfg, self.scope, level_id,
                                     dtype=Asp.dtype):
                from amgx_tpu.amg.device_setup import DeviceSetupOverflow

                try:
                    out = build_classical_level_device(
                        Asp, self.cfg, self.scope, level_id
                    )
                except DeviceSetupOverflow as e:
                    # the one known device limit: an ESC expansion past
                    # int32 addressing.  This level goes to the host
                    # (scipy int64) builder; every other device-setup
                    # failure raises — a silent host rebuild would hide
                    # a broken device pipeline
                    import warnings

                    warnings.warn(
                        f"device setup level {level_id}: {e}; building "
                        "this level on the host"
                    )
                else:
                    self.level_builders.append("device")
                    return (*out, None)
            elif explicit_device:
                import warnings

                warnings.warn(
                    "setup_location=DEVICE but the config is not "
                    "covered by the device pipeline; using HOST"
                )
        from amgx_tpu.amg.classical import build_classical_level

        self.level_builders.append("host")
        return (*build_classical_level(Asp, self.cfg, self.scope,
                                       level_id), None)

    def _new_smoother(self) -> Solver:
        """Un-set-up smoother instance for this config (the restore
        path sets it up by state import instead of ``setup``)."""
        name, sscope = self.cfg.get_scoped("smoother", self.scope)
        return make_nested(SolverRegistry.get(name)(self.cfg, sscope))

    def _make_smoother(self, A: SparseMatrix) -> Solver:
        sm = self._new_smoother()
        sm.setup(A)
        return sm

    def _new_coarse_solver(self, A: SparseMatrix):
        """Un-set-up coarse-solver instance for this config and
        coarsest operator, or None (NOSOLVER / dense size gate).  The
        store-restore path imports state into it instead of running
        ``setup``."""
        name, cscope = self.cfg.get_scoped("coarse_solver", self.scope)
        if name == "NOSOLVER":
            return None
        if name in ("DENSE_LU_SOLVER", "DENSE_LU"):
            # reference amg.cu:211: the max-rows cap applies only when
            # dense_lu_max_rows != 0
            if 0 < self.dense_lu_max_rows < A.n_rows:
                return None
        cs = make_nested(SolverRegistry.get(name)(self.cfg, cscope))
        from amgx_tpu.solvers.inexact import InexactCoarseSolver

        if isinstance(cs, InexactCoarseSolver):
            # the inexact sweep budget is tolerance-linked through the
            # cycle depth (solvers/inexact.py)
            cs.cycle_depth = len(self.levels)
        return cs

    def _make_coarse_solver(self, A: SparseMatrix):
        cs = self._new_coarse_solver(A)
        if cs is not None:
            cs.setup(A)
        return cs

    def _accel_formats(self):
        """Acceleration formats hierarchy operators build with: the
        matrix_free knob prepends the MATRIX_FREE stencil format (each
        format still subject to its own gate — non-stencil operators
        fall through to DIA/dense/ELL exactly as before)."""
        if self.matrix_free:
            return ("matrix_free", "dia", "dense", "ell")
        return ("dia", "dense", "ell")

    def _maybe_matrix_free(self, A: SparseMatrix):
        """Rebuild the finest operator with the MATRIX_FREE format when
        the knob is on and detection succeeds; returns the ORIGINAL
        object (identity, memos intact) otherwise.  User uploads build
        with the default formats, so the upgrade re-runs ``from_csr``
        over the host CSR triple — host-resident so the compact state
        rides the one batched finalize transfer."""
        if (
            not self.matrix_free
            or A.block_size != 1
            or not A.is_square
            or A.partition is not None
            or A.has_matrix_free
        ):
            return A
        new = SparseMatrix.from_csr(
            np.asarray(A.row_offsets),
            np.asarray(A.col_indices),
            np.asarray(A.values),
            n_cols=A.n_cols,
            views=A.views,
            accel_formats=self._accel_formats(),
            validate=False,
            device=False,
        )
        return new if new.has_matrix_free else A

    def _setup_impl(self, A: SparseMatrix):
        from amgx_tpu.ops.diagonal import scalarized

        self.setup_profile = {}
        self.level_builders = []
        with setup_profile_scope(self.setup_profile):
            # block systems: the scalar expansion is host-resident —
            # as levels[0].A it rides the batched finalize transfer,
            # keeping ≤1 batch per cold setup
            A = scalarized(A, "AMG", device=False)
            A = self._maybe_matrix_free(A)
            self.levels = [AMGLevel(A, 0)]
            # read the finest operator back through the
            # construction-time host memo instead of a device->host
            # download
            with setup_phase("host_csr", "transfer"):
                Asp = A.host_csr()
            self._coarsen_from(Asp)
            self._finalize_setup()
            # the finest operator's lazy host memo served the
            # coarsening read-back; drop it now for the same reason
            # _upload_levels never propagates memos onto coarse
            # levels — a set-up hierarchy must not pin host CSR
            # copies for its lifetime (it re-materializes lazily if
            # ever needed again; zero-copy on CPU)
            try:
                object.__delattr__(
                    self.levels[0].A, "_host_csr_cache"
                )
            except AttributeError:
                pass
        self._maybe_dump_setup_profile()

    def _maybe_dump_setup_profile(self):
        from amgx_tpu.core.profiling import (
            setup_profile_dump_enabled,
            setup_profile_table,
        )

        if setup_profile_dump_enabled() and self.setup_profile:
            from amgx_tpu.core.printing import emit

            emit(
                "AMG setup profile "
                f"(levels={len(self.levels)}):\n"
                + setup_profile_table(self.setup_profile)
            )

    def _coarsen_from(self, Asp):
        """Extend ``self.levels`` by coarsening from the last level
        (whose host CSR is ``Asp``) until a stop condition hits.

        Every matrix this loop builds is HOST-RESIDENT
        (``device=False``) — the whole coarsening chain strength ->
        select -> P -> Galerkin stays in numpy, and ``_finalize_setup``
        ships the finished hierarchy to the device in one batched
        transfer (``_upload_levels``)."""
        self.setup_stats["coarsen_calls"] += 1
        # reference amg.cu:207-230: when the coarse solver is dense LU,
        # coarsening stops once the level fits the dense trigger size
        coarse_name, _ = self.cfg.get_scoped("coarse_solver", self.scope)
        stop_rows = self.min_coarse_rows
        if coarse_name in ("DENSE_LU_SOLVER", "DENSE_LU"):
            stop_rows = max(stop_rows, self.dense_lu_num_rows)
        while True:
            lvl = self.levels[-1]
            n = lvl.n_rows
            if (
                len(self.levels) >= self.max_levels
                or n <= stop_rows
                or n <= self.min_fine_rows
            ):
                break
            with setup_level(lvl.level_id):
                P, R, Ac, geo = self._build_coarse(Asp, lvl.level_id)
                nc = Ac.shape[0]
                # stall: empty, non-shrinking, or shrinking slower than
                # coarsen_threshold allows (reference amg.cu:365-370)
                if nc >= n or nc == 0 or nc > self.coarsen_threshold * n:
                    break
                dtype = lvl.A.values.dtype
                if self.coarse_reorder != "NONE":
                    # coarse numbering is internal: renumber gather-bound
                    # Galerkin operators for column locality (windowed kernel)
                    from amgx_tpu.ops.reorder import reorder_coarse_level

                    with setup_phase("reorder"):
                        P, R, Ac = reorder_coarse_level(P, R, Ac, dtype)
                with setup_phase("level_formats"):
                    # geometric aggregates: P and R take the structured
                    # grid transfers once verified against the blocked
                    # map (a reordered P fails the check and keeps ELL)
                    lvl.P = SparseMatrix.from_scipy(
                        P.astype(dtype, copy=False), device=False,
                        geo_transfer=geo,
                    )
                    lvl.R = SparseMatrix.from_scipy(
                        R.astype(dtype, copy=False), device=False,
                        geo_transfer=geo,
                    )
                    Ac = Ac.astype(dtype, copy=False)
                if self.structure_reuse != 0:
                    with setup_phase("rap_plan"):
                        lvl.rap_plan = self._try_plan_rap(R, Asp, P, Ac)
                with setup_phase("level_formats"):
                    # Galerkin products of constant stencils on divisible
                    # grids stay constant stencils, so the matrix-free format
                    # propagates down the hierarchy (each level re-verified
                    # independently)
                    Ac_dev = SparseMatrix.from_scipy(
                        Ac, device=False,
                        accel_formats=self._accel_formats(),
                    )
                self.levels.append(AMGLevel(Ac_dev, len(self.levels)))
                self.setup_stats["levels_built"] += 1
                Asp = Ac

    @staticmethod
    def _try_plan_rap(R, Asp, P, Ac):
        """Numeric-Galerkin plan for structure reuse, or None when the
        stored coarse pattern doesn't cover the product (truncation,
        geometric dense-reduction with dropped entries)."""
        from amgx_tpu.amg.spgemm import plan_rap

        try:
            Acc = Ac.tocsr().copy()
            Acc.sort_indices()
            return plan_rap(R.tocsr(), Asp.tocsr(), P.tocsr(), Acc,
                            device=False)
        except ValueError:
            return None

    @staticmethod
    def _is_host_resident(obj) -> bool:
        return obj is not None and any(
            isinstance(leaf, np.ndarray)
            for leaf in jax.tree_util.tree_leaves(obj)
        )

    def _upload_levels(self):
        """Batched finalize (the tentpole transfer discipline): ship
        every host-resident leaf the deferred coarsening produced —
        all levels' CSR/ELL/DIA values, gather maps, P/R and Galerkin
        plan index lists — in ONE batched ``jax.device_put`` (the same
        lever the store restore path measured ~10x on,
        store/serialize.py unflatten).  Device-resident objects (the
        finest operator, restored levels) are left untouched so object
        identity — which the artifact store dedups on — is preserved."""
        sites = []  # (level, field_name, host_resident_obj)
        for lvl in self.levels:
            for name in ("A", "P", "R", "rap_plan"):
                obj = getattr(lvl, name)
                if self._is_host_resident(obj):
                    sites.append((lvl, name, obj))
        if not sites:
            return
        leaves, treedef = jax.tree_util.tree_flatten(
            [obj for _, _, obj in sites]
        )
        dev_objs = jax.tree_util.tree_unflatten(
            treedef, setup_transfer(leaves)
        )
        for (lvl, name, old), new in zip(sites, dev_objs):
            if isinstance(old, SparseMatrix):
                # structure fingerprint rides along; the host-CSR memo
                # deliberately does NOT — the coarsening that needed it
                # is over, and propagating it would pin every level's
                # full host CSR for the hierarchy's lifetime
                old._propagate_structure_memo(new)
            setattr(lvl, name, new)

    # ------------------------------------------------------------------
    # per-level precision policy (cheap preconditioner)

    def _hierarchy_dtype(self):
        """Target numpy dtype of the reduced-precision policy, or None
        (hierarchy_dtype=SAME, or complex operators).  A target equal
        to the input dtype is returned too — the casts are then
        identity no-ops (``astype`` short-circuits)."""
        spec = _HIERARCHY_DTYPES.get(self.hierarchy_dtype)
        if spec is None:
            return None
        dt = np.dtype(spec)
        if self.levels:
            fine = np.dtype(self.levels[0].A.values.dtype)
            if fine.kind == "c":
                # complex hierarchies have no reduced-precision twin
                # registered; keep them untouched
                return None
        return dt

    def _cast_level_ids(self, dt):
        """Level ids whose OPERATOR the policy casts (P/R always cast
        when a target dtype is set — transfer bandwidth is the point)."""
        if dt is None:
            return set()
        first = 0 if self.level_dtype_policy == "ALL" else 1
        return {lvl.level_id for lvl in self.levels[first:]}

    def _cast_hierarchy(self):
        """Apply the per-level precision policy in place — called at
        the top of ``_finalize_setup`` so host-resident cast values
        ride the ONE batched ``_upload_levels`` transfer and smoothers
        / the coarse solver set up on the cast operators.  Idempotent:
        ``SparseMatrix.astype`` short-circuits on a matching dtype, so
        resetups and store restores never churn objects."""
        dt = self._hierarchy_dtype()
        if dt is None:
            return
        cast_ids = self._cast_level_ids(dt)
        for lvl in self.levels:
            if lvl.level_id in cast_ids:
                lvl.A = lvl.A.astype(dt)
            for name in ("P", "R"):
                m = getattr(lvl, name)
                if m is not None:
                    setattr(lvl, name, m.astype(dt))

    def _check_restored_dtypes(self):
        """Store-restore guardrail: a persisted hierarchy whose level
        dtypes contradict this config's precision policy is a STALE
        artifact (e.g. an all-f64 payload whose manifest was rewritten
        for a mixed-precision config) — restoring it would silently
        serve the wrong-precision hierarchy as a warm hit.  Raises
        :class:`~amgx_tpu.core.errors.StoreError`, which every store
        consumer counts as a miss."""
        from amgx_tpu.core.errors import StoreError

        dt = self._hierarchy_dtype()
        if dt is None:
            return
        cast_ids = self._cast_level_ids(dt)
        for lvl in self.levels:
            got = [
                (name, np.dtype(m.values.dtype))
                for name, m in (
                    ("A", lvl.A if lvl.level_id in cast_ids else None),
                    ("P", lvl.P),
                    ("R", lvl.R),
                )
                if m is not None and np.dtype(m.values.dtype) != dt
            ]
            if got:
                raise StoreError(
                    f"persisted hierarchy level {lvl.level_id} carries "
                    f"{got[0][0]} values of dtype {got[0][1]} but this "
                    f"config's precision policy wants {dt} — stale "
                    "artifact, counted as a miss"
                )

    def _check_restored_formats(self):
        """Store-restore guardrail (sibling of
        ``_check_restored_dtypes``): a persisted hierarchy whose
        acceleration formats contradict the ``matrix_free`` knob is a
        STALE artifact — it either carries matrix-free compact state
        this config would never build (knob off), or stores O(nnz) DIA
        planes for a finest operator this config's setup would verify
        and compress (knob on — checked by re-running detection, an
        O(nnz) host compare on bytes the restore already shipped).
        Raises :class:`~amgx_tpu.core.errors.StoreError`, which every
        store consumer counts as a miss."""
        from amgx_tpu.core.errors import StoreError

        if not self.matrix_free:
            for lvl in self.levels:
                if lvl.A.has_matrix_free:
                    raise StoreError(
                        f"persisted hierarchy level {lvl.level_id} "
                        "carries MATRIX_FREE compact state but this "
                        "config has matrix_free=0 — stale artifact, "
                        "counted as a miss"
                    )
            return
        A = self.levels[0].A
        if (
            A.has_matrix_free
            or not A.has_dia
            or A.dia_src is None
            or A.block_size != 1
        ):
            return
        from amgx_tpu.ops.stencil import detect_stencil_np

        det = detect_stencil_np(
            A.dia_offsets, np.asarray(A.dia_vals),
            np.asarray(A.dia_src), A.n_rows,
        )
        if det is not None:
            raise StoreError(
                "persisted hierarchy finest level is a verified "
                "stencil but stores DIA planes while this config has "
                "matrix_free=1 — stale artifact, counted as a miss"
            )

    def _refresh_smoother(self, lvl: AMGLevel):
        """Level-smoother refresh policy: a surviving smoother (the
        values-only resetup path keeps level objects) RESETUPS in
        place — so smoothers with pattern-level cached setup state
        (Chebyshev/OPT_POLYNOMIAL spectral bounds) keep their cache
        instead of re-estimating per resetup (the PR 8 bound-caching
        fix; ``reestimate_eigs`` forces a refresh cadence).  Fresh
        levels build a new smoother as before."""
        if lvl.smoother is None:
            lvl.smoother = self._make_smoother(lvl.A)
        else:
            lvl.smoother.resetup(lvl.A)

    def _finalize_setup(self, reuse_smoothers: bool = False):
        # precision policy BEFORE the batched upload: cast values are
        # host-resident at cold setup, so the reduced bytes are what
        # ships; smoothers and the coarse solver then derive their
        # state from the cast operators
        with setup_phase("cast"):
            self._cast_hierarchy()
        self._upload_levels()
        # smoothers on all but the coarsest; coarse solver on the last.
        # reuse_smoothers (store-restore path ONLY): keep smoothers the
        # importer already restored — setup/resetup must NOT pass it
        # (their level values changed, so smoother params must refresh)
        with setup_phase("finalize"):
            for lvl in self.levels[:-1]:
                if not (reuse_smoothers and lvl.smoother is not None):
                    with setup_level(lvl.level_id):
                        self._refresh_smoother(lvl)
        coarsest = self.levels[-1]
        # the coarse-solver build gets its own profiler phase: a
        # DenseLU bottom's O(n^3) factorization used to hide inside
        # "finalize", which made the coarse_solver=INEXACT win
        # invisible in setup_profile and the
        # amgx_setup_phase_seconds_total family
        with setup_phase("coarse_factor", "device"), \
                setup_level(coarsest.level_id):
            restored = getattr(self, "_restored_coarse", None)
            self._restored_coarse = None
            if reuse_smoothers and restored is not None:
                self.coarse_solver = restored
            else:
                self.coarse_solver = self._make_coarse_solver(
                    coarsest.A
                )
        with setup_phase("finalize"):
            if self.coarse_solver is None and len(self.levels) > 0:
                # coarsest-level smoothing fallback
                # (coarse_solver=NOSOLVER)
                if not (reuse_smoothers and coarsest.smoother is not None):
                    self._refresh_smoother(coarsest)

        # levels whose P and R both took the structured grid transfers
        self.setup_profile["geo_transfer_levels"] = sum(
            lvl.P is not None
            and lvl.P.has_geo_transfer
            and lvl.R.has_geo_transfer
            for lvl in self.levels
        )
        # A, P and R matrices that took the windowed-ELL arrays (the
        # kernel on a TPU), and each one's mean touched x rows per
        # 1024-row tile, keyed "l<level>.<role>"
        touched = {
            f"l{lvl.level_id}.{role}": float(np.asarray(M.ell_wcount).mean())
            for lvl in self.levels
            for role, M in (("A", lvl.A), ("P", lvl.P), ("R", lvl.R))
            if M is not None and M.ell_wcount is not None
        }
        self.setup_profile["well_matrices"] = len(touched)
        self.setup_profile["well_touched_rows"] = touched
        self._params = self._collect_params()
        # reference solver.cu:541-546: grid stats and vis data print
        # only at verbosity_level > 2
        if self.print_grid_stats and self.verbosity > 2:
            from amgx_tpu.core.printing import emit

            emit(self.grid_stats())
        if bool(self.cfg.get("print_vis_data", self.scope)) \
                and self.verbosity > 2:
            from amgx_tpu.core.printing import emit

            emit(self.vis_data())

    def _resetup_impl(self, A: SparseMatrix) -> bool:
        """Values-only refresh (reference structure_reuse_levels /
        replace_coefficients): re-evaluate the top Galerkin products on
        device via the stored plans, rebuild any unplanned tail on host."""
        if self.structure_reuse == 0 or not self.levels:
            return False
        from amgx_tpu.ops.diagonal import scalarized

        A = scalarized(A, "AMG")
        lvl0 = self.levels[0]
        if A.n_rows != lvl0.A.n_rows or A.nnz != lvl0.A.nnz:
            return False
        self.setup_profile = {}
        self.level_builders = []
        with setup_profile_scope(self.setup_profile):
            lvl0.A = lvl0.A.replace_values(A.values)
            depth = len(self.levels) - 1
            if self.structure_reuse > 0:
                depth = min(self.structure_reuse, depth)
            i = 0
            with setup_phase("rap_execute"):
                while i < depth and self.levels[i].rap_plan is not None:
                    lvl = self.levels[i]
                    ac_vals = lvl.rap_plan.apply(
                        lvl.R.values, lvl.A.values, lvl.P.values
                    )
                    nxt = self.levels[i + 1]
                    nxt.A = nxt.A.replace_values(ac_vals)
                    i += 1
            if i < len(self.levels) - 1:
                # tail not refreshable in place: re-coarsen from level i
                del self.levels[i + 1:]
                self.levels[i].P = self.levels[i].R = None
                self.levels[i].rap_plan = None
                self._coarsen_from(self.levels[i].A.to_scipy())
            self._finalize_setup()
        return True

    # ------------------------------------------------------------------
    # setup persistence (amgx_tpu.store): the hierarchy IS the setup —
    # persist the level chain (operators, transfers, Galerkin plans)
    # and rebuild only the cheap derived state (smoothers, coarse LU)
    # at import.  Smoother/coarse params re-derive deterministically
    # from the bitwise-identical persisted level operators, so the
    # restored solver's iteration counts match the original exactly.

    def _export_impl(self):
        if not self.levels:
            return None
        # per-level smoother state rides along so smoothers with
        # non-trivial setup (Chebyshev spectrum estimation) restore
        # instead of re-deriving; the smoother's operator is the
        # level's (object-identity dedup stores it once).  Smoothers
        # whose export fails (exotic state) fall back to re-derivation
        # at import — same result, just not amortized.
        levels = []
        for lvl in self.levels:
            sm = None
            if lvl.smoother is not None:
                try:
                    sm = lvl.smoother._export_setup()
                except Exception:  # noqa: BLE001 — re-derive at import
                    sm = None
            levels.append({
                "A": lvl.A,
                "P": lvl.P,
                "R": lvl.R,
                "plan": lvl.rap_plan,
                "smoother": sm,
            })
        # coarse-solver state rides along like the smoothers': a
        # DenseLU bottom restores its factors instead of re-paying the
        # O(n^3) factorization, INEXACT restores its inner spectral
        # bounds.  Best-effort — unexportable state re-derives at
        # import from the bitwise-identical coarsest operator.
        coarse = None
        if self.coarse_solver is not None:
            try:
                coarse = {
                    "name": self.coarse_solver.registry_name,
                    "state": self.coarse_solver._export_setup(),
                }
            except Exception:  # noqa: BLE001 — re-derive at import
                coarse = None
        return {"levels": levels, "coarse": coarse}

    def _import_impl(self, impl):
        if not impl or not impl.get("levels"):
            return self._setup_impl(self.A)
        self.levels = []
        for state in impl["levels"]:
            lvl = AMGLevel(state["A"], len(self.levels))
            lvl.P = state.get("P")
            lvl.R = state.get("R")
            lvl.rap_plan = state.get("plan")
            sm_state = state.get("smoother")
            if sm_state is not None:
                try:
                    sm = self._new_smoother()
                    sm._import_setup(sm_state)
                    lvl.smoother = sm
                except Exception:  # noqa: BLE001 — finalize re-derives
                    lvl.smoother = None
            self.levels.append(lvl)
        # stale-artifact guardrail BEFORE finalize: _cast_hierarchy
        # would silently "repair" wrong-dtype levels, turning a stale
        # payload into a wrong-provenance warm hit
        self._check_restored_dtypes()
        self._check_restored_formats()
        self._restored_coarse = None
        cs_state = impl.get("coarse")
        if cs_state:
            try:
                cs = self._new_coarse_solver(self.levels[-1].A)
                if (
                    cs is not None
                    and cs.registry_name == cs_state.get("name")
                ):
                    cs._import_setup(cs_state["state"])
                    self._restored_coarse = cs
            except Exception:  # noqa: BLE001 — finalize re-derives
                self._restored_coarse = None
        self.setup_profile = {}
        self.setup_stats["restored"] = True
        self._finalize_setup(reuse_smoothers=True)

    def make_batch_params(self):
        """Traced values-only hierarchy rebuild (the batched analogue
        of ``_resetup_impl``): the finest coefficients flow down the
        Galerkin chain through the stored RAP plans, each level's
        smoother params rebuild from its level values, and the coarse
        solver re-factorizes — all inside one jit/vmap program, so one
        vmapped call re-evaluates a whole group's hierarchies
        (:mod:`amgx_tpu.serve`).  Transfer operators P/R keep their
        setup-time weights, exactly like ``structure_reuse_levels``.

        Requires planned Galerkin products on every transition and
        batch-capable smoothers/coarse solver; returns None otherwise.
        """
        if not self.levels or self.levels[0].A.block_size != 1:
            return None
        lvls = self.levels
        if any(lvl.rap_plan is None for lvl in lvls[:-1]):
            return None
        sm = []
        for lvl in lvls:
            if lvl.smoother is None:
                sm.append(None)
                continue
            s = lvl.smoother.make_batch_params()
            if s is None:
                return None
            sm.append(s)
        cs = None
        if self.coarse_solver is not None:
            cs = self.coarse_solver.make_batch_params()
            if cs is None:
                return None
        n_lv = len(lvls)
        sm_fns = [None if s is None else s[1] for s in sm]
        cs_fn = None if cs is None else cs[1]
        # per-level value dtypes (mixed-precision policy): the traced
        # rebuild must hand every level's consumers — operator swap,
        # smoother params, coarse refactorization — values in the
        # dtype the setup-time hierarchy carries, exactly like
        # _resetup_impl's replace_values path casts
        lvl_dts = tuple(lvl.A.values.dtype for lvl in lvls)
        template = dict(
            As=tuple(lvl.A for lvl in lvls),
            Ps=tuple(lvl.P for lvl in lvls[:-1]),
            Rs=tuple(lvl.R for lvl in lvls[:-1]),
            plans=tuple(lvl.rap_plan for lvl in lvls[:-1]),
            smoothers=tuple(None if s is None else s[0] for s in sm),
            coarse=None if cs is None else cs[0],
        )

        def fn(t, v):
            lvl_vals = [_to_dtype(v, lvl_dts[0])]
            for i in range(n_lv - 1):
                lvl_vals.append(
                    _to_dtype(
                        t["plans"][i].apply(
                            t["Rs"][i].values, lvl_vals[i],
                            t["Ps"][i].values,
                        ),
                        lvl_dts[i + 1],
                    )
                )
            per_level = []
            for i in range(n_lv):
                Ai = t["As"][i].replace_values(lvl_vals[i])
                P = t["Ps"][i] if i < n_lv - 1 else None
                R = t["Rs"][i] if i < n_lv - 1 else None
                smp = (
                    sm_fns[i](t["smoothers"][i], lvl_vals[i])
                    if sm_fns[i] is not None
                    else None
                )
                per_level.append((Ai, P, R, smp))
            coarse = (
                cs_fn(t["coarse"], lvl_vals[-1])
                if cs_fn is not None
                else None
            )
            return tuple(per_level), coarse

        return template, fn

    def _collect_params(self):
        per_level = []
        for lvl in self.levels:
            per_level.append(
                (
                    lvl.A,
                    lvl.P,
                    lvl.R,
                    lvl.smoother.apply_params() if lvl.smoother else None,
                )
            )
        coarse = (
            self.coarse_solver.apply_params() if self.coarse_solver else None
        )
        return (tuple(per_level), coarse)

    # ------------------------------------------------------------------
    # cycles (reference fixed_cycle.cu FixedCycle::cycle)

    # W/F cycles branch twice per level; full branching unrolls 2^depth
    # coarse visits into the XLA program.  Branch only on the top levels
    # (truncated gamma-cycle) to bound trace size; below that the walk
    # degenerates to V, where the extra visits are numerically negligible
    # (coarse solves are near-exact there anyway).  Shared with the
    # distributed cycle (distributed/amg.py).
    _W_MAX_BRANCH_LEVELS = W_MAX_BRANCH_LEVELS

    def _level_sweeps(self, lvl_id):
        pre, post = self.presweeps, self.postsweeps
        if lvl_id == 0 and self.finest_sweeps >= 0:
            # reference fixed_cycle.cu:197-201: finest_sweeps overrides both
            # sweep counts on the finest level (kept zero if configured zero)
            pre = 0 if pre == 0 else self.finest_sweeps
            post = 0 if post == 0 else self.finest_sweeps
        return pre, post

    def make_cycle(self):
        """Pure fn(params, b, x) -> x : one multigrid cycle.

        Mixed-precision hierarchies (hierarchy_dtype): each level's
        work runs in that level's value dtype — the restricted rhs
        casts DOWN entering a cheaper level and the prolonged
        correction casts back UP at the transfer boundary, so the
        coarse-grid bandwidth (the bulk of a V-cycle's bytes) moves at
        the reduced width.  All casts are no-ops for single-dtype
        hierarchies (``_to_dtype``)."""
        n_levels = len(self.levels)
        lvl_dts = [lvl.A.values.dtype for lvl in self.levels]
        # fused descent legs (ops/stencil.py): static per-level — only
        # matrix-free operators qualify (the win is zero coefficient
        # traffic; fusing a DIA leg would still stream the planes)
        fused_lvls = [
            self.fused_cycle and lvl.A.has_matrix_free
            for lvl in self.levels
        ]
        smooth_fns = [
            lvl.smoother.make_smooth() if lvl.smoother else None
            for lvl in self.levels
        ]
        coarse_apply = (
            self.coarse_solver.make_apply() if self.coarse_solver else None
        )
        cycle_type = self.cycle_type
        error_scaling = self.error_scaling
        scaling_steps = max(self.scaling_smoother_steps, 0)
        vanek_steps = max(self.postsweeps, 1)

        def _scaled_correction(A, smooth_fn, smp, b, x, r, e):
            """x + lambda*e with the error_scaling lambda (reference
            aggregation_amg_level.cu:696-805)."""
            vanek = error_scaling > 3
            if vanek and smooth_fn is not None:
                # smooth the correction against rhs 0, x against b,
                # then refresh the residual (Vanek scheme)
                e = smooth_fn(smp, jnp.zeros_like(e), e, vanek_steps)
                x = smooth_fn(smp, b, x, vanek_steps)
                r = b - spmv(A, x)
            elif scaling_steps > 0 and smooth_fn is not None:
                e = smooth_fn(smp, r, e, scaling_steps)
            Ae = spmv(A, e)
            if error_scaling in (2, 4):
                num, den = dot(r, Ae), dot(Ae, Ae)
            else:  # 3, 5
                num, den = dot(r, e), dot(e, Ae)
            lam = jnp.where(den != 0, num / jnp.where(den != 0, den, 1.0),
                            1.0)
            return x + lam * e

        def cycle(params, b, x, lvl_id=0):
            level_params, coarse_params = params
            A, P, R, smp = level_params[lvl_id]
            # named scopes tag the emitted HLO so device traces break the
            # cycle down per level/phase (NVTX-range analogue, SURVEY
            # §5.1; reference fixed_cycle.cu levelProfile tics)
            if lvl_id == n_levels - 1:
                with named_scope("amg_coarse_solve"):
                    if coarse_apply is not None:
                        # error-correction form is exact for direct
                        # solvers and safe for nonzero x (reference
                        # launchCoarseSolver).  The correction casts
                        # back to the level dtype: a sub-f32 level's
                        # DenseLU factors solve in f32
                        return x + _to_dtype(
                            coarse_apply(
                                coarse_params, b - spmv(A, x)
                            ),
                            x.dtype,
                        )
                    return smooth_fns[lvl_id](
                        smp, b, x, self.coarsest_sweeps
                    )
            pre, post = self._level_sweeps(lvl_id)
            if fused_lvls[lvl_id]:
                # one fine-grid pass for the whole descent leg
                # (identical arithmetic to the unfused sequence below
                # — parity is bitwise; ops/stencil.py records the pass)
                with named_scope(f"amg_l{lvl_id}_fused_leg"):
                    x, r, bc = fused_cycle_leg(
                        A, R, smooth_fns[lvl_id], smp, b, x, pre
                    )
                    bc = _to_dtype(bc, lvl_dts[lvl_id + 1])
            else:
                if pre > 0:
                    with named_scope(f"amg_l{lvl_id}_presmooth"):
                        x = smooth_fns[lvl_id](smp, b, x, pre)
                with named_scope(f"amg_l{lvl_id}_restrict"):
                    r = b - spmv(A, x)
                    bc = _to_dtype(spmv(R, r), lvl_dts[lvl_id + 1])
            xc = jnp.zeros(
                (R.n_rows * R.block_size,), dtype=lvl_dts[lvl_id + 1]
            )
            branch = lvl_id < min(
                n_levels - 2, self._W_MAX_BRANCH_LEVELS
            )
            if cycle_type == "W" and branch:
                xc = cycle(params, bc, xc, lvl_id + 1)
                xc = cycle(params, bc, xc, lvl_id + 1)
            elif cycle_type == "F" and branch:
                xc = cycle(params, bc, xc, lvl_id + 1)
                xc = _v_cycle(params, bc, xc, lvl_id + 1)
            elif cycle_type in ("CG", "CGF") and branch:
                xc = _kcycle_solve(params, bc, lvl_id + 1)
            else:
                xc = cycle(params, bc, xc, lvl_id + 1)
            with named_scope(f"amg_l{lvl_id}_prolong"):
                if self.error_scaling >= 2:
                    x = _scaled_correction(
                        A, smooth_fns[lvl_id], smp, b, x, r,
                        _to_dtype(spmv(P, xc), x.dtype))
                else:
                    x = x + _to_dtype(spmv(P, xc), x.dtype)
            if post > 0:
                with named_scope(f"amg_l{lvl_id}_postsmooth"):
                    x = smooth_fns[lvl_id](smp, b, x, post)
            return x

        def _kcycle_solve(params, b, lvl_id):
            """K-cycle (reference cycles/cg_[flex_]cycle.cu, Notay): the
            coarse problem is solved by cycle_iters (F)CG iterations
            preconditioned with the recursive cycle at this level."""
            level_params, _ = params
            A = level_params[lvl_id][0]
            flexible = cycle_type == "CGF"
            x = jnp.zeros((A.n_rows * A.block_size,), b.dtype)
            r = b
            with named_scope(f"amg_l{lvl_id}_kcycle_precond"):
                z = cycle(params, r, jnp.zeros_like(r), lvl_id)
            p = z
            rho = dot(r, z)
            for j in range(self.cycle_iters):
                with named_scope(f"amg_l{lvl_id}_kcycle_spmv"):
                    q = spmv(A, p)
                pq = dot(p, q)
                alpha = jnp.where(pq != 0, rho / pq, 0.0)
                x = x + alpha * p
                r_new = r - alpha * q
                if j + 1 == self.cycle_iters:
                    break
                z = cycle(params, r_new, jnp.zeros_like(r_new), lvl_id)
                rho_new = dot(r_new, z)
                if flexible:
                    beta = dot(z, r_new - r) / jnp.where(
                        rho != 0, rho, 1.0
                    )
                else:
                    beta = rho_new / jnp.where(rho != 0, rho, 1.0)
                p = z + beta * p
                r, rho = r_new, rho_new
            return x

        def _v_cycle(params, b, x, lvl_id):
            level_params, coarse_params = params
            A, P, R, smp = level_params[lvl_id]
            if lvl_id == n_levels - 1:
                with named_scope("amg_coarse_solve"):
                    if coarse_apply is not None:
                        return x + _to_dtype(
                            coarse_apply(
                                coarse_params, b - spmv(A, x)
                            ),
                            x.dtype,
                        )
                    return smooth_fns[lvl_id](
                        smp, b, x, self.coarsest_sweeps
                    )
            pre, post = self._level_sweeps(lvl_id)
            if fused_lvls[lvl_id]:
                with named_scope(f"amg_l{lvl_id}_fused_leg"):
                    x, r, bc = fused_cycle_leg(
                        A, R, smooth_fns[lvl_id], smp, b, x, pre
                    )
                    bc = _to_dtype(bc, lvl_dts[lvl_id + 1])
            else:
                if pre > 0:
                    with named_scope(f"amg_l{lvl_id}_presmooth"):
                        x = smooth_fns[lvl_id](smp, b, x, pre)
                with named_scope(f"amg_l{lvl_id}_restrict"):
                    r = b - spmv(A, x)
                    bc = _to_dtype(spmv(R, r), lvl_dts[lvl_id + 1])
            xc = jnp.zeros(
                (R.n_rows * R.block_size,), dtype=lvl_dts[lvl_id + 1]
            )
            xc = _v_cycle(params, bc, xc, lvl_id + 1)
            with named_scope(f"amg_l{lvl_id}_prolong"):
                if error_scaling >= 2:
                    x = _scaled_correction(
                        A, smooth_fns[lvl_id], smp, b, x, r,
                        _to_dtype(spmv(P, xc), x.dtype))
                else:
                    x = x + _to_dtype(spmv(P, xc), x.dtype)
            if post > 0:
                with named_scope(f"amg_l{lvl_id}_postsmooth"):
                    x = smooth_fns[lvl_id](smp, b, x, post)
            return x

        return cycle

    # ------------------------------------------------------------------
    # Solver interface: one cycle per iteration (reference
    # AlgebraicMultigrid_Solver::solve_iteration, amg.cu:1102-1117)

    def operator_of(self, params):
        level_params, _ = params
        return level_params[0][0]  # finest-level A

    def make_step(self):
        cycle = self.make_cycle()
        fine_dt = self.levels[0].A.values.dtype

        def step(params, b, x):
            # preconditioner boundary cast (level_dtype_policy=ALL
            # under an f64 outer solver): the whole cycle — finest
            # smoothing included — runs in the hierarchy dtype, and
            # the correction returns at the caller's precision.  The
            # f64 accuracy envelope is the OUTER solver's job
            # (RefinementSolver / monitored Krylov residuals).
            if b.dtype == fine_dt:
                return cycle(params, b, x)
            return _to_dtype(
                cycle(
                    params, _to_dtype(b, fine_dt), _to_dtype(x, fine_dt)
                ),
                b.dtype,
            )

        return step

    # make_apply: inherited — base Solver composes make_smooth over
    # make_step (= one cycle per iteration), matching the reference's
    # AMG-preconditioner usage with max_iters cycles.

    def cycle_passes_per_iteration(self):
        """Fine-grid operator passes one cycle executes, counted by
        tracing ``make_cycle`` under
        :data:`amgx_tpu.ops.spmv.op_pass_counter` — the number behind
        the ``amgx_solver_cycle_passes_total`` telemetry family and
        the ci/matrix_free_bench.py fused-leg gate (each fused
        descent leg contributes exactly ONE pass; the unfused
        reference leg contributes one per smoother sweep plus the
        residual).  Cached per setup (``_jit_cache`` clears on
        setup/resetup)."""
        key = "__cycle_passes_per_iteration__"
        if key in self._jit_cache:
            return self._jit_cache[key]
        try:
            if not self.levels:
                val = None
            else:
                cycle = self.make_cycle()
                params = self.apply_params()
                A0 = self.levels[0].A
                spec = jax.ShapeDtypeStruct(
                    (A0.n_rows * A0.block_size,),
                    jnp.zeros((), A0.values.dtype).dtype,
                )
                with op_pass_counter() as c:
                    jax.eval_shape(cycle, params, spec, spec)
                val = c.count
        except Exception:  # noqa: BLE001 — accounting must never fail
            val = None
        self._jit_cache[key] = val
        return val

    # ------------------------------------------------------------------

    def vis_data(self) -> str:
        """Per-level structure dump (reference print_vis_data /
        amg_level printVisData: writes grid/aggregate visualization
        data; here a compact per-level structural summary)."""
        lines = ["         AMG visualization data:"]
        for lvl in self.levels:
            pr = lvl.P.nnz if lvl.P is not None else 0
            lines.append(
                f"           level {lvl.level_id}: rows={lvl.n_rows} "
                f"nnz={lvl.nnz} interp_nnz={pr} "
                f"avg_row_nnz={lvl.nnz / max(lvl.n_rows, 1):.2f}"
            )
        return "\n".join(lines)

    def grid_stats(self) -> str:
        """Grid statistics table (reference AMG::printGridStatistics,
        README.md:104-117 output contract)."""
        rows = []
        total_rows = total_nnz = 0
        bytes_total = 0
        for lvl in self.levels:
            n, nnz = lvl.n_rows, lvl.nnz
            total_rows += n
            total_nnz += nnz
            # measured bytes: every array leaf the level holds on
            # device (operator + transfers), not a model — the per-
            # level HBM figure users tune against (reference
            # memory_info.h "Mem Usage")
            lvl_bytes = 0
            for obj in (lvl.A, lvl.P, lvl.R):
                if obj is None:
                    continue
                for leaf in jax.tree_util.tree_leaves(obj):
                    if hasattr(leaf, "nbytes"):
                        lvl_bytes += int(leaf.nbytes)
            bytes_total += lvl_bytes
            sp = nnz / (n * n) if n else 0.0
            rows.append(
                f"         {lvl.level_id:>5}(D)"
                f" {n:>10} {nnz:>12} {sp:>10.3g}"
                f" {lvl_bytes / 2**30:>9.2e}"
            )
        fine = self.levels[0]
        grid_cx = total_rows / fine.n_rows if fine.n_rows else 0
        op_cx = total_nnz / fine.nnz if fine.nnz else 0
        head = (
            "         Number of Levels: %d\n" % len(self.levels)
            + "            LVL         ROWS          NNZ    SPRSTY"
            "       Mem (GB)\n"
            + "         " + "-" * 56
        )
        tail = (
            "         " + "-" * 56 + "\n"
            f"         Grid Complexity: {grid_cx:.5g}\n"
            f"         Operator Complexity: {op_cx:.5g}\n"
            f"         Total Memory Usage: "
            f"{bytes_total / 2**30:.6g} GB"
        )
        return "\n".join([head] + rows + [tail])
