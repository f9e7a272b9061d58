"""Block-CSR sparse matrix as a JAX pytree.

Reference parity: Matrix<TConfig> (include/matrix.h:65, src/matrix.cu) —
block-CSR with optional external diagonal, views for distributed overlap,
and a computeDiagonal step.  TPU-first differences:

  * The matrix is an immutable pytree of static-shape device arrays plus
    static metadata, so it can flow through ``jit``/``shard_map`` and be
    donated between solve calls.  "replace_coefficients"
    (amgx_c.h:281-286) is ``dataclasses.replace`` on the value arrays with
    identical structure -> no retrace.
  * Alongside CSR we build an ELL (padded fixed-width rows) acceleration
    structure whenever padding overhead is acceptable.  ELL turns SpMV into
    a dense gather + reduction, which XLA tiles well on TPU; CSR falls back
    to a segment-sum formulation.  This replaces the reference's block-size
    specialized CUDA kernels (src/multiply.cu:49-71) and cuSPARSE bsrmv.
  * Views (INTERIOR/BOUNDARY/OWNED/FULL/ALL, vector.h:18-27) are static
    (offset, size) windows stored in metadata; distributed code slices with
    them at trace time.

Construction happens on host (numpy); setup-phase code (coarsening,
Galerkin products) manipulates scipy.sparse and converts back.
"""

from __future__ import annotations

import dataclasses
import hashlib
from typing import Any, Optional

import numpy as np
import jax
import jax.numpy as jnp

from amgx_tpu.core.types import ViewType

# Maximum ELL padding blow-up relative to true nnz before we give up on the
# ELL acceleration structure and use pure CSR segment-sum SpMV.
_ELL_MAX_OVERHEAD = 4.0
# Hard cap on ELL row width regardless of overhead.
_ELL_MAX_WIDTH = 128
# DIA (diagonal) acceleration structure: built when the matrix has few
# distinct diagonals and acceptable padding.  DIA SpMV is shift+FMA — no
# gather — which is the fast path on TPU (XLA gathers are slow; stencil
# matrices like Poisson 5/7/27-pt are pure DIA).
_DIA_MAX_DIAGS = 48
_DIA_MAX_OVERHEAD = 2.0
# Dense acceleration structure: small unstructured matrices (AMG coarse
# Galerkin operators lose banded structure) store a dense copy so SpMV is
# a matmul on the MXU — cheaper than TPU gathers below this row count
# (4096^2 f32 = 64 MB).
_DENSE_MAX_ROWS = 4096


def _static_field(**kw):
    return dataclasses.field(metadata=dict(static=True), **kw)


def _want_tiled_ell(dtype) -> bool:
    """Build the Pallas tiled-ELL arrays?  TPU backends with a TPU-
    native dtype only (the kernel's tiling is f32/bf16-shaped; the XLA
    fallback uses the plain layout); AMGX_TPU_TILED_ELL=1/0 overrides
    (tests force-build on CPU to exercise the interpret-mode kernel)."""
    import os

    env = os.environ.get("AMGX_TPU_TILED_ELL")
    if env is not None:
        return env == "1"
    if np.dtype(dtype) not in (np.dtype(np.float32), np.dtype(jnp.bfloat16)):
        return False
    try:
        return jax.default_backend() == "tpu"
    except Exception:
        return False


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SparseMatrix:
    """Square-or-rectangular block-CSR matrix.

    Scalar matrices have ``block_size == 1`` and ``values.shape == (nnz,)``;
    block matrices store ``values.shape == (nnz, b, b)`` (row-major blocks,
    matching the reference default).  Vectors paired with a block matrix are
    flat ``(n_rows * b,)`` arrays.

    Data fields (traced):
      row_offsets: (n_rows+1,) int32 CSR row pointers
      col_indices: (nnz,) int32 column (block-)indices
      values:      (nnz,) or (nnz, b, b)
      row_ids:     (nnz,) int32 — row index of each stored entry (for
                   segment-sum SpMV); redundant with row_offsets but cheap
                   and avoids runtime expansion.
      diag:        (n_rows,) or (n_rows, b, b) — extracted diagonal
                   (reference Matrix::computeDiagonal, matrix.cu).
      ell_cols/ell_vals: optional ELL arrays, (n_rows, w[, b, b]); padding
                   entries have col 0 / value 0 so no mask is needed.
    """

    row_offsets: jnp.ndarray
    col_indices: jnp.ndarray
    values: jnp.ndarray
    row_ids: jnp.ndarray
    diag: jnp.ndarray
    ell_cols: Optional[jnp.ndarray]
    ell_vals: Optional[jnp.ndarray]
    # Windowed tiled ELL (ops.pallas_well layout) for the Pallas
    # lane-gather SpMV kernel: per-row-tile column windows with local
    # ids, and the window rows each tile touches (sorted, padded) with
    # their counts; built on TPU backends when the kernel's estimate
    # beats the XLA gather (pallas_well.build_windowed_ell).
    ell_wcols: Optional[jnp.ndarray] = None
    ell_wvals: Optional[jnp.ndarray] = None
    ell_wbase: Optional[jnp.ndarray] = None
    ell_wrows: Optional[jnp.ndarray] = None
    ell_wcount: Optional[jnp.ndarray] = None
    # DIA structure: dia_vals[k, i] = A[i, i + dia_offsets[k]] (0 outside)
    dia_vals: Optional[jnp.ndarray] = None
    # dense copy for small unstructured matrices (SpMV = MXU matmul)
    dense: Optional[jnp.ndarray] = None
    # First-occurrence gather maps (slot -> nnz index, -1 = empty):
    # replace_values rebuilds diag/dia_vals/ell_vals with GATHERS
    # instead of scatters — scatter is the slow op on both CPU XLA and
    # TPU, and the serve layer re-runs these rebuilds per batched
    # call.  Assumes canonical CSR (duplicate (row, col) entries, when
    # present at all, are zero-valued beyond the first — true for
    # from_coo-deduplicated uploads and serve bucket padding).
    diag_src: Optional[jnp.ndarray] = None
    dia_src: Optional[jnp.ndarray] = None
    ell_src: Optional[jnp.ndarray] = None
    # MATRIX_FREE compact stencil state (ops/stencil.py): when the
    # matrix is a verified constant / axis-separable stencil, the O(nnz)
    # DIA planes are REPLACED by O(nd) / O(nd * axis) coefficients
    # (mf_coefs) regenerated on the fly by the apply, plus a
    # first-occurrence gather map into the CSR values (mf_src) so
    # replace_values re-derives coefficients per value swap.
    mf_coefs: Optional[jnp.ndarray] = None
    mf_src: Optional[jnp.ndarray] = None
    # structured grid transfer (ops/geo_transfer.py): P's fine-length
    # weights when the matrix is a verified geometric-aggregation P or
    # R = P^T whose values are not all ones; None otherwise
    geo_w: Optional[jnp.ndarray] = None

    n_rows: int = _static_field(default=0)
    n_cols: int = _static_field(default=0)
    block_size: int = _static_field(default=1)
    dia_offsets: Any = _static_field(default=None)  # tuple[int] | None
    # static stencil description (ops.stencil.StencilMeta) of the
    # MATRIX_FREE state; None = format not built
    mf_meta: Any = _static_field(default=None)
    # static grid-transfer description (ops.geo_transfer.GeoTransferMeta)
    # of a verified geometric-aggregation P or R; None = format not built
    geo_meta: Any = _static_field(default=None)
    # windowed-ELL column-window width in lanes (static); None = no
    # windowed arrays
    ell_wwidth: Any = _static_field(default=None)
    # Static view windows: {ViewType: (row_offset, num_rows)}; populated by the
    # distributed manager.  Single-device matrices map every view to (0, n).
    views: Any = _static_field(default=None)
    # Distributed partition info (amgx_tpu.distributed.manager.PartitionInfo)
    # — static metadata; None for single-device matrices.  Mirrors
    # Matrix::getManager (reference matrix.h:180).
    partition: Any = _static_field(default=None)

    # ---- basic properties ----------------------------------------------

    @property
    def nnz(self) -> int:
        return self.col_indices.shape[0]

    @property
    def dtype(self):
        return self.values.dtype

    @property
    def shape(self):
        return (self.n_rows, self.n_cols)

    @property
    def has_ell(self) -> bool:
        return self.ell_cols is not None

    @property
    def has_dia(self) -> bool:
        return self.dia_offsets is not None

    @property
    def has_dense(self) -> bool:
        return self.dense is not None

    @property
    def has_matrix_free(self) -> bool:
        return self.mf_meta is not None

    @property
    def has_geo_transfer(self) -> bool:
        return self.geo_meta is not None

    @property
    def is_square(self) -> bool:
        return self.n_rows == self.n_cols

    def view_rows(self, view: ViewType) -> int:
        """Number of rows covered by a view window (prefix windows only)."""
        if self.views is None:
            return self.n_rows
        off, size = self.views[view]
        assert off == 0
        return size

    def fingerprint(self) -> str:
        """Sparsity fingerprint: a stable hash of the STRUCTURE only
        (row_offsets, col_indices, shape, block size) — values excluded.
        Two matrices with equal fingerprints accept each other's
        coefficient arrays (``replace_values``), which is what the
        batched solve service (:mod:`amgx_tpu.serve`) groups on.  The
        hash is computed once per object and memoized (the index
        arrays are immutable device buffers)."""
        fp = getattr(self, "_fingerprint_cache", None)
        if fp is None:
            fp = sparsity_fingerprint(
                np.asarray(self.row_offsets),
                np.asarray(self.col_indices),
                self.n_rows,
                self.n_cols,
                self.block_size,
            )
            # frozen dataclass: memoize around the freeze (the cache is
            # not a field, so pytree transforms simply drop it)
            object.__setattr__(self, "_fingerprint_cache", fp)
        return fp

    def setup_key(self) -> tuple:
        """``(sparsity_fingerprint, dtype string)`` — the identity the
        setup-artifact store (:mod:`amgx_tpu.store`) keys hierarchies
        on.  The dtype half is always read LIVE from the value buffer
        (never memoized): ``astype`` and value-swapping paths must not
        be able to serve a stale dtype to the store."""
        return self.fingerprint(), str(np.dtype(self.values.dtype))

    def _propagate_structure_memo(self, new: "SparseMatrix"):
        """Carry the memoized sparsity fingerprint onto a derived
        matrix whose INDEX structure is identical (values-only
        rebuilds).  Value-dependent memos must never ride along — only
        the structure hash is copied, and only when one exists.
        Traced-value twins (vmap/jit leaves) share the same structure,
        so this is safe under transforms too."""
        fp = getattr(self, "_fingerprint_cache", None)
        if fp is not None:
            object.__setattr__(new, "_fingerprint_cache", fp)
        return new

    # ---- value updates (structure reuse) -------------------------------

    def replace_values(self, values, diag=None) -> "SparseMatrix":
        """Refresh coefficients keeping structure — the
        AMGX_matrix_replace_coefficients fast path (amgx_c.h:281-286).

        Traced and vmap-safe; acceleration-structure values rebuild by
        gather when the ``*_src`` maps exist (see their field comment),
        falling back to scatter forms otherwise."""
        values = jnp.asarray(values, dtype=self.values.dtype).reshape(
            self.values.shape
        )
        if diag is None:
            if self.diag_src is not None:
                diag = _gather_src(self.diag_src, values)
            else:
                diag = _extract_diag_jnp(self, values)
        new = dataclasses.replace(self, values=values, diag=diag)
        if self.has_ell:
            if self.ell_src is not None:
                ell_vals = _gather_src(self.ell_src, values)
            else:
                ell_vals = _scatter_ell_vals(self, values)
            new = dataclasses.replace(new, ell_vals=ell_vals)
            if self.ell_wvals is not None:
                # the windowed layout stores values in plain tiled
                # order (only columns are localized)
                from amgx_tpu.ops.pallas_well import tile_ell_jnp

                new = dataclasses.replace(
                    new, ell_wvals=tile_ell_jnp(ell_vals)
                )
        if self.has_dia:
            if self.dia_src is not None:
                dia_vals = _gather_src(self.dia_src, values)
            else:
                dia_vals = _scatter_dia_vals(self, values)
            new = dataclasses.replace(new, dia_vals=dia_vals)
        if self.has_matrix_free:
            # re-derive the compact stencil coefficients from the new
            # values; assumes the swap preserves the stencil class
            # (same contract as sparsity: the serve/resetup callers
            # refresh VALUES of the operator detection verified)
            new = dataclasses.replace(
                new, mf_coefs=_gather_src(self.mf_src, values)
            )
        if self.has_geo_transfer:
            # P's values are its fine-length weights; R's are the same
            # weights in coarse-row order
            w = values
            if self.geo_meta.transpose:
                w = jnp.zeros_like(values).at[self.col_indices].set(values)
            new = dataclasses.replace(new, geo_w=w)
        if self.has_dense:
            d = jnp.zeros_like(self.dense)
            d = d.at[self.row_ids, self.col_indices].add(values)
            new = dataclasses.replace(new, dense=d)
        # dataclasses.replace builds a FRESH object, so every memoized
        # attribute is dropped by construction — value-dependent memos
        # (setup_key dtype, store digests) can never go stale through a
        # values-only rebuild.  The structure fingerprint alone is
        # still valid (indices untouched) and is re-attached so
        # resetup/serve paths don't rehash the pattern per swap.
        return self._propagate_structure_memo(new)

    def astype(self, dtype) -> "SparseMatrix":
        if np.dtype(dtype) == np.dtype(self.values.dtype):
            # identity cast returns SELF: memos (fingerprint, host
            # CSR) and object identity — which the artifact store
            # dedups on and the hierarchy cast policy re-applies
            # idempotently — survive by construction
            return self
        rep = dict(
            values=self.values.astype(dtype), diag=self.diag.astype(dtype)
        )
        if self.has_ell:
            rep["ell_vals"] = self.ell_vals.astype(dtype)
            if self.ell_wvals is not None:
                rep["ell_wvals"] = self.ell_wvals.astype(dtype)
        if self.has_dia:
            rep["dia_vals"] = self.dia_vals.astype(dtype)
        if self.has_matrix_free:
            rep["mf_coefs"] = self.mf_coefs.astype(dtype)
        if self.geo_w is not None:
            rep["geo_w"] = self.geo_w.astype(dtype)
        if self.has_dense:
            rep["dense"] = self.dense.astype(dtype)
        # structure is unchanged (fingerprint excludes values/dtype);
        # anything dtype-keyed is dropped with the fresh object and
        # setup_key() re-reads the dtype live
        return self._propagate_structure_memo(
            dataclasses.replace(self, **rep)
        )

    # ---- host conversions ----------------------------------------------

    @staticmethod
    def from_csr(
        row_offsets,
        col_indices,
        values,
        n_cols=None,
        block_size=1,
        build_ell=True,
        views=None,
        partition=None,
        dtype=None,
        accel_formats=("dia", "dense", "ell"),
        validate=None,
        device=True,
        geo_transfer=None,
    ) -> "SparseMatrix":
        """Build from host CSR arrays (also the upload path — reference
        AMGX_matrix_upload_all, amgx_c.h:262-279).

        ``accel_formats`` restricts which acceleration structures may
        build (each still subject to its own gate); ``build_ell=False``
        disables all of them.  The serve bucketing layer passes
        ``("dense",)``: the dense structure is the only one whose
        static metadata is pattern-independent, so bucketed matrices
        sharing it also share XLA programs.

        ``validate`` (default: on unless ``AMGX_TPU_VALIDATE=0``) runs
        the cheap structural/numeric guardrails (core/errors.py):
        malformed CSR raises ``PatternDegeneracyError``, NaN/Inf
        coefficients raise ``NonFiniteValuesError`` — typed at the
        upload boundary instead of a NaN solve status much later.

        ``device=False`` builds a HOST-RESIDENT matrix: every array
        leaf stays numpy so a caller constructing many matrices (the
        AMG coarsening loop) can ship them all in ONE batched
        ``jax.device_put`` later (per-array puts cost ~0.5 ms each —
        the dominant per-level setup cost the batched finalize
        removes).  Host-resident matrices are construction-time
        intermediates: solve paths expect device leaves.

        ``geo_transfer`` is the ``(grid, block)`` of a geometric
        aggregation (``select_aggregates``): when the matrix is verified
        to be that aggregation's P or R (ops/geo_transfer.py) it takes
        the structured-transfer format instead of DIA/dense/ELL.
        """
        from amgx_tpu.core import errors as _errors
        from amgx_tpu.core.profiling import setup_phase

        with setup_phase("formats"):
            row_offsets = np.asarray(row_offsets, dtype=np.int32)
            col_indices = np.asarray(col_indices, dtype=np.int32)
            values = np.asarray(values)
            if dtype is not None:
                values = values.astype(dtype)
        n_rows = row_offsets.shape[0] - 1
        if n_cols is None:
            n_cols = n_rows
        if validate is None:
            validate = _errors.validation_enabled()
        if validate:
            with setup_phase("validate"):
                _errors.validate_csr(
                    row_offsets, col_indices, values, n_rows, n_cols,
                    block_size=block_size,
                )
        with setup_phase("formats"):
            host = SparseMatrix._host_formats(
                row_offsets, col_indices, values, n_rows, n_cols,
                block_size, build_ell, partition, accel_formats,
                geo_transfer,
            )
        if device:
            with setup_phase("upload", "transfer"):
                arrays = {
                    k: None if v is None else jnp.asarray(v)
                    for k, v in host["arrays"].items()
                }
                jax.block_until_ready(arrays)
        else:
            arrays = host["arrays"]  # host-resident build
        m = SparseMatrix(
            **arrays,
            ell_wwidth=host["ell_wwidth"],
            n_rows=int(n_rows),
            n_cols=int(n_cols),
            block_size=int(block_size),
            dia_offsets=host["dia_offsets"],
            mf_meta=host["mf_meta"],
            geo_meta=host["geo_meta"],
            views=views,
            partition=partition,
        )
        if device:
            from amgx_tpu.core import profiling

            # eager per-matrix upload: counts as one transfer batch
            # when a setup profile is active (the AMG set-up's single
            # batched finalize is asserted against this hook)
            if profiling.active_setup_profile() is not None:
                n_arr = sum(v is not None for v in arrays.values())
                profiling.count_setup_transfer(n_arr)
        return m

    @staticmethod
    def _host_formats(row_offsets, col_indices, values, n_rows, n_cols,
                      b, build_ell, partition, accel_formats,
                      geo_transfer=None) -> dict:
        """The host arrays of a ``from_csr`` build: CSR, diagonal and
        the acceleration formats each gate admits."""
        if b == 1:
            values = values.reshape(-1)
        else:
            values = values.reshape(-1, b, b)
        nnz = col_indices.shape[0]
        assert values.shape[0] == nnz, (values.shape, nnz)

        row_lens = np.diff(row_offsets)
        row_ids = np.repeat(np.arange(n_rows, dtype=np.int32), row_lens)
        diag = _extract_diag_np(row_offsets, col_indices, values, n_rows, b)
        diag_src = None
        if nnz:
            # unbuffered minimum: FIRST occurrence wins (plain fancy
            # assignment iterates in memory order, not array order)
            sentinel = np.iinfo(np.int32).max
            diag_src = np.full(n_rows, sentinel, dtype=np.int32)
            hit_idx = np.nonzero(col_indices == row_ids)[0]
            np.minimum.at(
                diag_src, row_ids[hit_idx], hit_idx.astype(np.int32)
            )
            diag_src[diag_src == sentinel] = -1

        geo_meta = geo_w = None
        if geo_transfer is not None and b == 1 and partition is None:
            from amgx_tpu.ops.geo_transfer import detect_geo_transfer_np

            det = detect_geo_transfer_np(
                row_offsets, col_indices, values, n_rows, n_cols,
                *geo_transfer,
            )
            if det is not None:
                # the grid reductions replace every other format
                geo_meta, geo_w = det
                build_ell = False

        dia_offsets = dia_vals = dia_src = None
        # build_ell=False opts out of ALL acceleration structures (DIA
        # included): bucketed/CSR-only matrices need
        # pattern-independent static metadata
        if (
            build_ell
            and "dia" in accel_formats
            and b == 1
            and n_rows == n_cols
            and nnz
        ):
            dia_offsets, dia_vals, dia_src = _try_build_dia_np(
                row_offsets, col_indices, values, row_ids, n_rows
            )

        mf_meta = mf_coefs = mf_src = None
        if (
            build_ell
            and "matrix_free" in accel_formats
            and b == 1
            and n_rows == n_cols
            and nnz
            and partition is None
        ):
            # detection consumes DIA planes; build them transiently if
            # the "dia" format wasn't requested / gated out
            trio = (dia_offsets, dia_vals, dia_src)
            if trio[0] is None:
                trio = _try_build_dia_np(
                    row_offsets, col_indices, values, row_ids, n_rows
                )
            if trio[0] is not None:
                from amgx_tpu.ops.stencil import detect_stencil_np

                det = detect_stencil_np(
                    trio[0], trio[1], trio[2], n_rows
                )
                if det is not None:
                    mf_meta, mf_coefs, mf_src = det
                    # the compact state REPLACES the O(nnz) DIA
                    # planes — that is the whole point of the format
                    dia_offsets = dia_vals = dia_src = None

        dense = None
        dense_bytes = n_rows * n_cols * values.dtype.itemsize
        if (
            build_ell  # opt-out flag covers all acceleration structures
            and "dense" in accel_formats
            and b == 1
            and dia_offsets is None
            and mf_meta is None
            and 0 < n_rows <= _DENSE_MAX_ROWS
            and n_cols <= _DENSE_MAX_ROWS
            and dense_bytes <= 64 * 1024 * 1024
        ):
            dense = np.zeros((n_rows, n_cols), dtype=values.dtype)
            np.add.at(dense, (row_ids, col_indices), values)

        ell_cols = ell_vals = ell_src = None
        windowed = None
        if (
            build_ell
            and "ell" in accel_formats
            and n_rows > 0
            and dia_offsets is None
            and mf_meta is None
            and dense is None
        ):
            w = int(row_lens.max()) if nnz else 0
            if w <= _ELL_MAX_WIDTH and w * n_rows <= _ELL_MAX_OVERHEAD * max(
                nnz, 1
            ):
                ell_cols, ell_vals, ell_src = _build_ell_np(
                    row_offsets, col_indices, values, n_rows, w, b
                )
                if b == 1 and w > 0 and _want_tiled_ell(values.dtype):
                    # the windowed kernel where its estimate beats the
                    # XLA gather over these ELL arrays; AMG set-up
                    # renumbers coarse unknowns (RCM) for locality
                    from amgx_tpu.ops.pallas_well import build_windowed_ell

                    windowed = build_windowed_ell(
                        row_offsets, ell_cols, ell_vals
                    )

        return {
            "arrays": dict(
                row_offsets=row_offsets,
                col_indices=col_indices,
                values=values,
                row_ids=row_ids,
                diag=diag,
                ell_cols=ell_cols,
                ell_vals=ell_vals,
                ell_wcols=None if windowed is None else windowed.cols,
                ell_wvals=None if windowed is None else windowed.vals,
                ell_wbase=None if windowed is None else windowed.bases,
                ell_wrows=None if windowed is None else windowed.rows,
                ell_wcount=None if windowed is None else windowed.counts,
                dia_vals=dia_vals,
                dense=dense,
                diag_src=diag_src,
                dia_src=dia_src,
                ell_src=ell_src,
                mf_coefs=mf_coefs,
                mf_src=mf_src,
                geo_w=geo_w,
            ),
            "ell_wwidth": None if windowed is None else windowed.width,
            "dia_offsets": dia_offsets,
            "mf_meta": mf_meta,
            "geo_meta": geo_meta,
        }

    @staticmethod
    def from_coo(
        rows, cols, vals, n_rows=None, n_cols=None, block_size=1, **kw
    ) -> "SparseMatrix":
        rows = np.asarray(rows)
        cols = np.asarray(cols)
        vals = np.asarray(vals)
        if n_rows is None:
            n_rows = int(rows.max()) + 1 if rows.size else 0
        if n_cols is None:
            n_cols = int(cols.max()) + 1 if cols.size else 0
        b = block_size
        order = np.lexsort((cols, rows))
        rows, cols = rows[order], cols[order]
        vals = vals.reshape(-1, b, b)[order] if b > 1 else vals[order]
        # Sum duplicates (reference upload tolerates none, but COO assembly
        # from FEM codes commonly has them).
        key = rows.astype(np.int64) * n_cols + cols
        uniq, inv = np.unique(key, return_inverse=True)
        if uniq.shape[0] != key.shape[0]:
            summed = np.zeros((uniq.shape[0],) + vals.shape[1:], vals.dtype)
            np.add.at(summed, inv, vals)
            vals = summed
            rows = (uniq // n_cols).astype(np.int32)
            cols = (uniq % n_cols).astype(np.int32)
        row_offsets = np.zeros(n_rows + 1, np.int32)
        np.add.at(row_offsets[1:], rows, 1)
        row_offsets = np.cumsum(row_offsets, dtype=np.int32)
        return SparseMatrix.from_csr(
            row_offsets, cols, vals, n_cols=n_cols, block_size=b, **kw
        )

    @staticmethod
    def from_scipy(sp, block_size=1, **kw) -> "SparseMatrix":
        sp = sp.tocsr()
        sp.sort_indices()
        if block_size == 1:
            return SparseMatrix.from_csr(
                sp.indptr, sp.indices, sp.data, n_cols=sp.shape[1], **kw
            )
        import scipy.sparse as sps

        bsr = sps.bsr_matrix(sp, blocksize=(block_size, block_size))
        bsr.sort_indices()
        return SparseMatrix.from_csr(
            bsr.indptr,
            bsr.indices,
            bsr.data,
            n_cols=sp.shape[1] // block_size,
            block_size=block_size,
            **kw,
        )

    def host_csr(self):
        """Scalar-expanded scipy CSR through a LAZY host memo: the
        first call materializes the CSR triple on host (``np.asarray``
        — zero-copy for host-resident builds and on the CPU backend, a
        one-time download on accelerators) and caches it, so repeated
        setups over the same operator never re-download.  Nothing is
        retained for matrices that never call this, and the memo reads
        the immutable device buffers — it can never desynchronize from
        the values the solve uses.

        READ-ONLY contract: the b==1 result shares the memoized numpy
        buffers — callers must not mutate it in place (the AMG setup
        chain builds fresh matrices at every stage and never does).
        ``to_scipy`` remains the mutable-copy API."""
        import scipy.sparse as sps

        cached = getattr(self, "_host_csr_cache", None)
        if cached is None:
            cached = (
                np.asarray(self.row_offsets),
                np.asarray(self.col_indices),
                np.asarray(self.values),
            )
            object.__setattr__(self, "_host_csr_cache", cached)
        ro, ci, v = cached
        if self.block_size == 1:
            # sortedness probes stay lazy: a raw from_csr upload may
            # carry unsorted columns, exactly like the to_scipy copy
            return sps.csr_matrix(
                (v, ci, ro), shape=(self.n_rows, self.n_cols),
                copy=False,
            )
        return sps.bsr_matrix(
            (v, ci, ro),
            shape=(
                self.n_rows * self.block_size,
                self.n_cols * self.block_size,
            ),
        ).tocsr()

    def to_scipy(self):
        """Expand (blocks unrolled to scalars) to scipy CSR — host side."""
        import scipy.sparse as sps

        b = self.block_size
        # copies: jax device buffers are read-only and scipy mutates in
        # place (sort_indices / eliminate_zeros)
        indptr = np.array(self.row_offsets)
        indices = np.array(self.col_indices)
        data = np.array(self.values)
        if b == 1:
            return sps.csr_matrix(
                (data, indices, indptr), shape=(self.n_rows, self.n_cols)
            )
        return sps.bsr_matrix(
            (data, indices, indptr),
            shape=(self.n_rows * b, self.n_cols * b),
        ).tocsr()

    def to_dense(self):
        sp = self.to_scipy()
        if sp.dtype.itemsize < 4:
            # scipy's kernels take no bfloat16: densify in f32 (exact)
            return np.asarray(sp.astype(np.float32).todense()).astype(
                sp.dtype)
        return np.asarray(sp.todense())


# ---------------------------------------------------------------------------
# host helpers


def sparsity_fingerprint(
    row_offsets, col_indices, n_rows, n_cols, block_size=1
) -> str:
    """Hash of a CSR sparsity pattern (host arrays).

    Stable across processes (content hash, not Python ``hash``): the
    serve hierarchy cache keys persist-ably on it.  Index dtypes are
    normalized to int32 first so an int64 upload and an int32 upload of
    the same pattern collide, as they must.
    """
    h = hashlib.blake2b(digest_size=16)
    h.update(
        np.asarray(
            [n_rows, n_cols, block_size, len(col_indices)], dtype=np.int64
        ).tobytes()
    )
    h.update(np.ascontiguousarray(row_offsets, dtype=np.int32).tobytes())
    h.update(np.ascontiguousarray(col_indices, dtype=np.int32).tobytes())
    return h.hexdigest()


def _row_ids_np(row_offsets, n_rows):
    return np.repeat(
        np.arange(n_rows, dtype=np.int32), np.diff(row_offsets)
    )


def _extract_diag_np(row_offsets, col_indices, values, n_rows, b):
    shape = (n_rows,) if b == 1 else (n_rows, b, b)
    diag = np.zeros(shape, dtype=values.dtype)
    row_ids = _row_ids_np(row_offsets, n_rows)
    hit = col_indices == row_ids
    # sum duplicates, matching the DIA/ELL/segment-sum SpMV paths
    np.add.at(diag, row_ids[hit], values[hit])
    return diag


def _build_ell_np(row_offsets, col_indices, values, n_rows, w, b):
    ell_cols = np.zeros((n_rows, w), dtype=np.int32)
    vshape = (n_rows, w) if b == 1 else (n_rows, w, b, b)
    ell_vals = np.zeros(vshape, dtype=values.dtype)
    ell_src = np.full((n_rows, w), -1, dtype=np.int32)
    row_ids = _row_ids_np(row_offsets, n_rows)
    pos = np.arange(col_indices.shape[0], dtype=np.int64) - row_offsets[
        row_ids
    ].astype(np.int64)
    ell_cols[row_ids, pos] = col_indices
    ell_vals[row_ids, pos] = values
    ell_src[row_ids, pos] = np.arange(
        col_indices.shape[0], dtype=np.int32
    )
    return ell_cols, ell_vals, ell_src


def dia_gate(num_diags: int, n: int, nnz: int) -> bool:
    """Single source of truth for DIA structure acceptance: few distinct
    diagonals with acceptable padding.  Shared with ops.reorder's
    would-build prediction."""
    return (
        num_diags <= _DIA_MAX_DIAGS
        and num_diags * n <= _DIA_MAX_OVERHEAD * max(nnz, 1)
    )


def _try_build_dia_np(row_offsets, col_indices, values, row_ids, n):
    """DIA structure if few distinct diagonals with acceptable padding."""
    offs = col_indices.astype(np.int64) - row_ids.astype(np.int64)
    uniq = np.unique(offs)
    if not dia_gate(uniq.shape[0], n, col_indices.shape[0]):
        return None, None, None
    dia_vals = np.zeros((uniq.shape[0], n), dtype=values.dtype)
    k = np.searchsorted(uniq, offs)
    # add (not assign): duplicate (row,col) entries must sum, matching the
    # ELL/segment-sum SpMV paths
    np.add.at(dia_vals, (k, row_ids), values)
    # unbuffered minimum: FIRST occurrence wins (replace_values
    # gather-rebuild; duplicates beyond the first must be zero-valued)
    sentinel = np.iinfo(np.int32).max
    dia_src = np.full((uniq.shape[0], n), sentinel, dtype=np.int32)
    idx = np.arange(col_indices.shape[0], dtype=np.int32)
    np.minimum.at(dia_src, (k, row_ids), idx)
    dia_src[dia_src == sentinel] = -1
    return tuple(int(o) for o in uniq), dia_vals, dia_src


def _gather_src(src, values):
    """Gather values into an acceleration-structure layout via a
    first-occurrence source map (-1 = empty slot).  The traced twin of
    the host builders; O(slots) gathers, no scatter."""
    v = values[jnp.clip(src, 0)]
    mask = (src >= 0).reshape(src.shape + (1,) * (values.ndim - 1))
    return jnp.where(mask, v, 0)


def _extract_diag_jnp(A: SparseMatrix, values):
    """Traced diagonal extraction for replace_values."""
    is_diag = A.col_indices == A.row_ids
    contrib = jnp.where(
        is_diag.reshape((-1,) + (1,) * (values.ndim - 1)), values, 0
    )
    return jax.ops.segment_sum(
        contrib, A.row_ids, num_segments=A.n_rows, indices_are_sorted=True
    )


def _scatter_dia_vals(A: SparseMatrix, values):
    """Rebuild dia_vals from updated CSR values (traced)."""
    offs = A.col_indices.astype(jnp.int64) - A.row_ids.astype(jnp.int64)
    uniq = jnp.asarray(A.dia_offsets, dtype=jnp.int64)
    k = jnp.searchsorted(uniq, offs)
    flat_idx = k * A.n_rows + A.row_ids
    out = jnp.zeros((len(A.dia_offsets) * A.n_rows,), values.dtype)
    return out.at[flat_idx].add(values).reshape(A.dia_vals.shape)


def _scatter_ell_vals(A: SparseMatrix, values):
    """Rebuild ell_vals from updated CSR values (traced)."""
    w = A.ell_cols.shape[1]
    starts = A.row_offsets[A.row_ids]
    pos_in_row = jnp.arange(A.nnz, dtype=jnp.int32) - starts
    flat_idx = A.row_ids * w + pos_in_row
    flat_shape = (A.n_rows * w,) + values.shape[1:]
    out = jnp.zeros(flat_shape, values.dtype).at[flat_idx].set(values)
    return out.reshape(A.ell_vals.shape)
