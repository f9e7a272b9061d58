"""Pallas TPU kernel for *windowed* ELL SpMV (gather-bound matrices).

Reference parity: cuSPARSE bsrmv (/root/reference/src/amgx_cusparse.cu:
49-102), the reference's fast path for unstructured matrices.

A TPU lane gather (``take_along_axis`` along lanes) works within one
128-lane vreg, so a kernel gathers from a table of x by walking the
table's vregs: one gather and one select per (table vreg, ELL slot)
for each 1024-row tile.  This kernel keeps that walk short:

  * rows are grouped in tiles of 1024 (8 sublanes x 128 lanes), ELL
    slots lane-interleaved (:func:`tile_ell`);
  * each tile stores a lane-aligned column-window base; column ids are
    stored *window-local*, so the kernel DMAs only ``x[base, base+W)``
    into VMEM (W, the widest tile's window, is static);
  * each tile also stores the sorted list of the window's 128-lane x
    rows its entries touch, and the kernel walks only those.

So a call costs about ``touched rows x slots`` walk steps per tile plus
one window copy per tile; the window's width enters only through the
copy and its VMEM.  :func:`build_windowed_ell` admits a matrix when the
window fits VMEM and that estimate, at the chip-measured cost of a step
(``_C_WALK``), beats the XLA gather over the plain ELL arrays at its
chip-measured cost per stored entry (``_C_GATHER``).  The decision reads
only the matrix's column structure, so A, P and R of every AMG level
and any user matrix go through the same gate.  AMG set-up renumbers
coarse unknowns for locality (RCM, ops/reorder.py), which shortens the
lists; a user matrix can be renumbered at the solver boundary.

On a TPU backend :func:`well_kernel_selected` picks the kernel for
every matrix that carries windowed arrays; a failure raises
(ops/kernel_gate.py).
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import numpy as np
import jax
import jax.numpy as jnp

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from amgx_tpu.ops.kernel_gate import on_tpu, xla_under_vmap


_SUB = 8
_LANE = 128
_ROW_TILE = _SUB * _LANE  # 1024 rows per grid step
# Window alignment (lanes): bases and widths are multiples of one
# (8, 128) vreg tile so the x-window DMA is a 2-D copy whose sublane
# start AND extent are multiples of 8 — the only DMA shape validated
# fault-free on real TPU (non-multiple-of-8 extents crash the worker;
# see ops/pallas_dia.py).
_WALIGN = _SUB * _LANE
# Largest window (lanes): the kernel holds two in VMEM (double
# buffered), so 4 MiB of f32 each stays inside the 16 MiB of scoped
# VMEM beside the double-buffered column and value blocks.
_WINDOW_MAX_LANES = 1 << 20
# The admission gate's cost model (TPU v5e, ci/well_constants.py):
# seconds per walk step of the kernel (one touched x row times one ELL
# slot of one tile) and per stored ELL entry of the XLA gather; the
# window copies stream at the chip's HBM rate.  The kernel's estimate
# must win by _KERNEL_MARGIN.
_C_WALK = 6e-9
_C_GATHER = 7.5e-9
_HBM_BYTES_PER_S = 819e9
_KERNEL_MARGIN = 2.0


class WindowedEll(NamedTuple):
    """The kernel's arrays of one matrix (host numpy)."""

    cols: np.ndarray  # (nt, 8, w*128) int32, window-local column ids
    vals: np.ndarray  # (nt, 8, w*128), tiled like tile_ell
    bases: np.ndarray  # (nt,) int32, lane-aligned window starts
    width: int  # W: window lanes, static
    rows: np.ndarray  # (nt, 1, K) int32, touched window rows, sorted
    counts: np.ndarray  # (nt,) int32, touched rows per tile


def tile_ell(ell_cols: np.ndarray, ell_vals: np.ndarray):
    """Host-side re-layout (n, w) -> (ntiles, 8, w*128), k-major lanes:

        tcols[t, s, k*128 + r] = ell_cols[t*1024 + s*128 + r, k]

    so slot ``k`` of the 128 rows of sublane group ``s`` occupies the
    contiguous lane window ``[k*128, (k+1)*128)`` and the (8, 128)
    output tile IS the y layout (flattening (t, s, r) row-major)."""
    n, w = ell_cols.shape
    pad = (-n) % _ROW_TILE
    if pad:
        ell_cols = np.pad(ell_cols, ((0, pad), (0, 0)))
        ell_vals = np.pad(ell_vals, ((0, pad), (0, 0)))
    nt = ell_cols.shape[0] // _ROW_TILE

    def arrange(a):
        a = a.reshape(nt, _SUB, _LANE, w)  # [t, s, r, k]
        a = a.transpose(0, 1, 3, 2)  # [t, s, k, r]
        return np.ascontiguousarray(a.reshape(nt, _SUB, w * _LANE))

    return arrange(ell_cols.astype(np.int32)), arrange(ell_vals)


def tile_ell_jnp(ell_vals):
    """Traced value-only re-layout matching :func:`tile_ell` — used by
    SparseMatrix.replace_values to refresh ell_wvals without leaving
    the jit trace.  Must stay in lockstep with tile_ell's geometry."""
    n, w = ell_vals.shape
    pad = (-n) % _ROW_TILE
    ev = jnp.pad(ell_vals, ((0, pad), (0, 0)))
    nt = ev.shape[0] // _ROW_TILE
    ev = ev.reshape(nt, _SUB, _LANE, w).transpose(0, 1, 3, 2)
    return ev.reshape(nt, _SUB, w * _LANE)


def _pad_up(v: int, m: int) -> int:
    return -(-v // m) * m


def tile_windowed_ell(
    row_offsets: np.ndarray,
    ell_cols: np.ndarray,
    ell_vals: np.ndarray,
):
    """Host-side windowed tiling of ELL arrays: a :class:`WindowedEll`,
    or ``None`` for an empty matrix or one whose widest tile window
    exceeds ``_WINDOW_MAX_LANES``.

    Padding slots (column 0, value 0) are masked out of the windows
    and of the touched rows; they keep local column 0 and value 0, so
    they add nothing whether or not the tile walks row 0.
    """
    n, w = ell_cols.shape
    if w == 0 or n == 0:
        return None
    row_lens = np.diff(row_offsets).astype(np.int64)
    real = np.arange(w)[None, :] < row_lens[:, None]  # (n, w)

    pad = (-n) % _ROW_TILE
    if pad:
        ell_cols = np.pad(ell_cols, ((0, pad), (0, 0)))
        ell_vals = np.pad(ell_vals, ((0, pad), (0, 0)))
        real = np.pad(real, ((0, pad), (0, 0)))
    nt = ell_cols.shape[0] // _ROW_TILE

    tc = ell_cols.reshape(nt, _ROW_TILE, w)
    tr = real.reshape(nt, _ROW_TILE, w)
    # per-tile min/max over real entries
    cmin = np.where(tr, tc, np.iinfo(np.int32).max).reshape(nt, -1).min(1)
    cmax = np.where(tr, tc, -1).reshape(nt, -1).max(1)
    empty = cmax < 0
    cmin[empty] = 0
    cmax[empty] = 0
    bases = (cmin // _WALIGN) * _WALIGN
    W = int(_pad_up(int((cmax - bases).max()) + 1, _WALIGN))
    if W > _WINDOW_MAX_LANES:
        return None
    local = np.where(tr, tc - bases[:, None, None], 0).astype(np.int32)

    # touched window rows: a (tile, row) bitmap whose extra last
    # column takes the padding slots
    nrow = W // _LANE
    hit = np.zeros((nt, nrow + 1), dtype=bool)
    hit[np.arange(nt)[:, None, None], np.where(tr, local // _LANE, nrow)] = (
        True
    )
    hit = hit[:, :nrow]
    counts = hit.sum(axis=1).astype(np.int32)
    t_idx, r_idx = np.nonzero(hit)  # tile-major, rows ascending
    pos = np.arange(t_idx.size) - (np.cumsum(counts) - counts)[t_idx]
    rows = np.zeros((nt, 1, max(int(counts.max()), 1)), dtype=np.int32)
    rows[t_idx, 0, pos] = r_idx

    tcols, tvals = tile_ell(local.reshape(n + pad, w), ell_vals)
    return WindowedEll(tcols, tvals, bases.astype(np.int32), W, rows,
                       counts)


def well_estimate_s(we: WindowedEll, n_rows: int) -> tuple:
    """``(kernel_s, gather_s)``: the gate's estimates of one SpMV over
    ``we`` on the kernel (its walk and window copies) and on the XLA
    gather over the plain (n_rows, w) ELL arrays."""
    w = we.cols.shape[2] // _LANE
    walk_s = _C_WALK * int(we.counts.sum(dtype=np.int64)) * w
    copy_s = (we.bases.shape[0] * we.width * we.vals.dtype.itemsize
              / _HBM_BYTES_PER_S)
    return walk_s + copy_s, _C_GATHER * n_rows * w


def build_windowed_ell(
    row_offsets: np.ndarray,
    ell_cols: np.ndarray,
    ell_vals: np.ndarray,
):
    """The matrix's :class:`WindowedEll` when the kernel is estimated
    to beat the XLA gather by ``_KERNEL_MARGIN``, else ``None``."""
    we = tile_windowed_ell(row_offsets, ell_cols, ell_vals)
    if we is None:
        return None
    kernel_s, gather_s = well_estimate_s(we, ell_cols.shape[0])
    return we if _KERNEL_MARGIN * kernel_s < gather_s else None


def _well_kernel(x_hbm, brows_ref, counts_ref, rows_ref, cols_ref,
                 vals_ref, o_ref, xwin, sem, *, w, W):
    t = pl.program_id(0)
    slot = t % 2

    def window(tile, s):
        # 2-D copy: sublane start (brow, multiple of 8) and extent
        # (W/128 rows, multiple of 8) are vreg-tile aligned — the
        # fault-free DMA shape (see _WALIGN)
        return pltpu.make_async_copy(
            x_hbm.at[pl.ds(brows_ref[tile], W // _LANE)], xwin.at[s],
            sem.at[s],
        )

    # the grid runs in order on one core: each step prefetches the
    # next tile's window into the other buffer
    @pl.when(t == 0)
    def _():
        window(0, 0).start()

    @pl.when(t + 1 < pl.num_programs(0))
    def _():
        window(t + 1, 1 - slot).start()

    # slot k of the tile's 1024 rows: window-local column ids, split
    # into the table vreg (hi) and the lane inside it (lo)
    cols = [cols_ref[0, :, k * _LANE:(k + 1) * _LANE] for k in range(w)]
    his = [c // _LANE for c in cols]
    los = [c % _LANE for c in cols]
    window(t, slot).wait()
    xw = xwin.at[slot]

    def table_vreg(i, gs):
        j = rows_ref[0, 0, i]
        tab = jnp.broadcast_to(xw[pl.ds(j, 1), :], (_SUB, _LANE))
        return tuple(
            jnp.where(
                hi == j,
                jnp.take_along_axis(
                    tab, lo, axis=1, mode="promise_in_bounds"
                ),
                g,
            )
            for hi, lo, g in zip(his, los, gs)
        )

    zero = jnp.zeros((_SUB, _LANE), o_ref.dtype)
    # walk only the window rows this tile touches
    gs = jax.lax.fori_loop(0, counts_ref[t], table_vreg, (zero,) * w)
    acc = zero
    for k, g in enumerate(gs):
        acc = acc + vals_ref[0, :, k * _LANE:(k + 1) * _LANE] * g
    o_ref[0] = acc


@functools.partial(
    jax.jit, static_argnames=("n_rows", "W", "interpret")
)
def _pallas_well_spmv(tcols, tvals, bases, trows, tcounts, x, n_rows, W,
                      interpret=False):
    """y = A @ x from windowed tiled ELL arrays."""
    nt, _, wl = tcols.shape
    w = wl // _LANE
    k = trows.shape[2]
    # pad x so every window read [base, base+W) is in bounds, to a
    # whole number of (8, 128) row tiles
    xlen = _pad_up(x.shape[0] + W, _WALIGN)
    xp = jnp.pad(x, (0, xlen - x.shape[0]))
    x2d = xp.reshape(-1, _LANE)
    brows = bases // _LANE  # multiples of 8 by construction

    out = pl.pallas_call(
        functools.partial(_well_kernel, w=w, W=W),
        grid=(nt,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, k), lambda t: (t, 0, 0),
                         memory_space=pltpu.SMEM),
            pl.BlockSpec((1, _SUB, wl), lambda t: (t, 0, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, _SUB, wl), lambda t: (t, 0, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (1, _SUB, _LANE), lambda t: (t, 0, 0),
            memory_space=pltpu.VMEM,
        ),
        # inside shard_map (the row-sharded solve) the output varies
        # over the same mesh axes as x
        out_shape=jax.ShapeDtypeStruct((nt, _SUB, _LANE), tvals.dtype,
                                       vma=jax.typeof(x).vma),
        scratch_shapes=[
            pltpu.VMEM((2, W // _LANE, _LANE), tvals.dtype),
            pltpu.SemaphoreType.DMA((2,)),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
    )(x2d, brows, tcounts, trows, tcols, tvals)
    return out.reshape(nt * _ROW_TILE)[:n_rows]


def well_kernel_selected(A) -> bool:
    """Does SpMV on ``A`` run the kernel (TPU backend + windowed arrays
    in a kernel dtype)?"""
    return (
        on_tpu()
        and A.ell_wcols is not None
        and A.ell_wvals.dtype in (jnp.float32, jnp.bfloat16)
    )


@functools.lru_cache(maxsize=None)
def _batchable(n_rows, W, interpret):
    from amgx_tpu.ops.spmv import ell_spmv_xla

    def kernel(ell_cols, ell_vals, tcols, tvals, bases, trows, tcounts, x):
        return _pallas_well_spmv(tcols, tvals, bases, trows, tcounts, x,
                                 n_rows, W, interpret=interpret)

    def xla(ell_cols, ell_vals, tcols, tvals, bases, trows, tcounts, x):
        return ell_spmv_xla(ell_cols, ell_vals, x)

    return xla_under_vmap(kernel, xla)


def pallas_well_spmv(A, x, interpret=False):
    """y = A @ x via the windowed kernel (A must carry windowed
    arrays); under ``jax.vmap`` the plain ELL arrays' XLA gather runs
    instead (ops/kernel_gate.py)."""
    return _batchable(A.n_rows, A.ell_wwidth, interpret)(
        A.ell_cols, A.ell_vals, A.ell_wcols, A.ell_wvals, A.ell_wbase,
        A.ell_wrows, A.ell_wcount, x,
    )
