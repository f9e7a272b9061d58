"""Sparse matrix-vector product.

Reference parity: multiply(A, B, C, view) with block-size dispatch
(src/multiply.cu:49-110) and cuSPARSE bsrmv (src/amgx_cusparse.cu:49-145).

TPU formulation: static-shape, jittable layouts ordered by speed.

  * structured grid transfer (the P and R = P^T of geometric
    aggregation, verified against the blocked map): box sums and
    broadcasts over the grid, an MXU product for the x blocks
    (:mod:`amgx_tpu.ops.geo_transfer`) — the vector streams once, no
    gather.
  * MATRIX_FREE (verified constant / axis-separable stencils): 3D
    shift+FMA with on-the-fly coefficients (:mod:`amgx_tpu.ops.stencil`,
    Pallas kernel in :mod:`amgx_tpu.ops.pallas_stencil`) — zero O(nnz)
    coefficient traffic.
  * DIA (stencil matrices): Pallas shift-FMA kernel
    (:mod:`amgx_tpu.ops.pallas_dia`) with an XLA shift+FMA fallback.
  * dense (small unstructured): one MXU matmul.
  * windowed ELL (unstructured): Pallas lane-gather kernel
    (:mod:`amgx_tpu.ops.pallas_well`), whose cost is the touched x rows
    times the ELL slots of each 1024-row tile, not the window's width;
    built where that walk is estimated cheaper than the XLA gather.
    RCM (:mod:`amgx_tpu.ops.reorder`) shortens the walk.
  * ELL: one XLA gather over the plain ELL arrays, at about the same
    cost per stored entry whatever the columns (and the windowed
    matrices' path under ``vmap``).
  * CSR (irregular fallback): gather per-nnz + ``segment_sum`` over
    precomputed sorted row ids.

The distributed SpMV with halo overlap (reference multiply.cu:95-110
exchange_halo_split_gather -> interior -> boundary) lives in
:mod:`amgx_tpu.distributed.solve`; this module is the single-shard
compute kernel it calls.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from amgx_tpu.core.matrix import SparseMatrix
from amgx_tpu.ops.blas import make_site_counter

# Trace-time operator-pass counter (the reduction_counter /
# psum_site_counter pattern, ops/blas.py): every SQUARE-operator SpMV
# call site records one fine-grid pass while a counter context is
# active.  The fused matrix-free cycle leg (ops/stencil.py) swallows
# its internal records and reports exactly one — tracing a cycle under
# ``op_pass_counter`` therefore PROVES the pass count per leg
# (ci/matrix_free_bench.py gate; amgx_solver_cycle_passes_total).
record_op_pass, op_pass_counter = make_site_counter("op_pass")


def spmv(A: SparseMatrix, x: jnp.ndarray, n_rows: int | None = None):
    """y = A @ x.

    x is flat (n_cols * block_size,).  Returns flat (n_rows * block_size,).
    ``n_rows`` restricts output to a leading row window (the view
    mechanism); default all rows.
    """
    if A.is_square:
        record_op_pass()
    b = A.block_size
    nr = A.n_rows if n_rows is None else n_rows
    if b == 1:
        y = _spmv_scalar(A, x)
    else:
        y = _spmv_block(A, x.reshape(A.n_cols, b)).reshape(-1)
    if nr != A.n_rows:
        y = y[: nr * b]
    return y


def _spmv_scalar(A, x):
    if A.has_geo_transfer:
        from amgx_tpu.ops.geo_transfer import geo_transfer_spmv

        return geo_transfer_spmv(A, x)
    if A.has_matrix_free:
        # compact stencil state: coefficients regenerate on the fly,
        # the only O(n) streams are x and y (ops/stencil.py)
        from amgx_tpu.ops.stencil import stencil_spmv

        return stencil_spmv(A, x)
    if A.has_dia:
        from amgx_tpu.ops.pallas_dia import (
            dia_kernel_selected,
            pallas_dia_spmv,
        )

        if dia_kernel_selected(A):
            return pallas_dia_spmv(A, x)
        return _spmv_dia(A, x)
    if A.has_dense:
        # small unstructured matrices: one MXU matmul beats TPU gathers
        return A.dense @ x
    if A.has_ell:
        from amgx_tpu.ops.pallas_well import (
            pallas_well_spmv,
            well_kernel_selected,
        )

        # the unstructured branches carry named scopes so a device
        # trace tells their ops from the cycle's elementwise work
        if well_kernel_selected(A):
            with jax.named_scope("spmv_well"):
                return pallas_well_spmv(A, x)
        with jax.named_scope("spmv_ell"):
            return ell_spmv_xla(A.ell_cols, A.ell_vals, x)
    with jax.named_scope("spmv_csr"):
        contrib = A.values * x[A.col_indices]
        return jax.ops.segment_sum(
            contrib, A.row_ids, num_segments=A.n_rows,
            indices_are_sorted=True
        )


def ell_spmv_xla(ell_cols, ell_vals, x):
    """ELL SpMV as one XLA gather: y_i = sum_k vals[i, k] * x[cols[i, k]].

    Gathered slot-major, (w, n): the TPU compiler takes up to ~130 s
    for the row-major (n, w) gather at some shapes (134,383 x 82;
    v5e, compiled without a chip) and ~1-3 s for this one at every
    shape tried."""
    return jnp.sum(ell_vals.T * x[ell_cols.T], axis=0)


def _spmv_dia(A, x):
    return dia_spmv_xla(A.dia_vals, x, tuple(A.dia_offsets), A.n_rows)


def dia_spmv_xla(dia_vals, x, offsets, n):
    """DIA SpMV: y_i = sum_k dia_vals[k, i] * x[i + off_k].

    Pure shift+FMA over contiguous slices of a padded x — no gather.  This
    is the XLA path for stencil-structured matrices (Poisson 5/7/27pt
    and friends); XLA fuses the whole sum into one bandwidth-bound pass.
    """
    pneg = max(0, -min(offsets))
    ppos = max(0, max(offsets))
    xpad = jnp.pad(x, (pneg, ppos))
    y = jnp.zeros_like(x, shape=(n,))
    for k, off in enumerate(offsets):
        y = y + dia_vals[k] * jax.lax.slice(
            xpad, (off + pneg,), (off + pneg + n,)
        )
    return y


def _spmv_block(A, x2d):
    if A.has_ell:
        with jax.named_scope("spmv_ell"):
            xg = x2d[A.ell_cols]  # (n, w, b)
            return jnp.einsum(
                "nwij,nwj->ni", A.ell_vals, xg,
                preferred_element_type=x2d.dtype
            )
    with jax.named_scope("spmv_csr"):
        xg = x2d[A.col_indices]  # (nnz, b)
        contrib = jnp.einsum(
            "nij,nj->ni", A.values, xg, preferred_element_type=x2d.dtype
        )
        return jax.ops.segment_sum(
            contrib, A.row_ids, num_segments=A.n_rows,
            indices_are_sorted=True
        )


def multiply(A: SparseMatrix, x, n_rows=None):
    """Alias matching the reference free function multiply() (multiply.h:14)."""
    return spmv(A, x, n_rows=n_rows)


def residual(A: SparseMatrix, b, x):
    """r = b - A x  (reference axmb / compute_residual, solver.cu)."""
    return b - spmv(A, x)
