"""Grid transfers of geometric aggregation as grid reductions.

Geometric aggregation (``geo_aggregate``, amg/aggregation.py) sends a
fine cell (ix, iy, iz) of an (nx, ny, nz) grid to the coarse cell
(ix // bx, iy // by, iz // bz), numbered lexicographically on the
coarse grid.  Its prolongation P (n x nc, one entry per row) and
restriction R = P^T are then not general sparse matrices: R is a sum
over (bx, by, bz) boxes and P a broadcast back onto them.  As CSR/ELL
both are gathers, which the TPU runs ~1000x below its HBM roofline;
here each streams the vector once with no gather:

  * restrict: the fine vector as (cz, bz * L) rows, L = cy * by * nx
    (zero-padded to whole y and z blocks); sum the bz column blocks;
    as (cz * cy, by * nx) rows, sum the by column blocks; then sum the
    x blocks with one MXU product against a constant 0/1 (kx, kx / bx)
    matrix at HIGHEST precision;
  * prolong: the same product with the transposed matrix expands the
    coarse vector along x (exact: every output is one input times 1),
    then side-by-side copies make the y and z blocks, and a slice drops
    the padding.

Every shape keeps a long minor dimension: the TPU pads a minor
dimension of 2 (the block) to 128 lanes, and a broadcast into one made
the 208^3 prolongation compile for ~47 s on a v5e (described, without a
chip).  The x step works on chunks of at most ``_X_CHUNK`` lanes so the
product's work per element stays bounded on wide grids.

The format is static metadata (:class:`GeoTransferMeta`) plus an
optional fine-length weight vector; it is attached only after
:func:`detect_geo_transfer_np` has verified the CSR arrays bit for bit
against the blocked map.
"""

from __future__ import annotations

from typing import NamedTuple, Tuple

import numpy as np
import jax
import jax.numpy as jnp
from jax import lax

# widest x run one MXU product spans (a multiple of every power-of-two
# block up to it); wider grids are padded to whole chunks
_X_CHUNK = 256


class GeoTransferMeta(NamedTuple):
    """Static (hashable) description of a structured grid transfer.

    grid:      fine (nx, ny, nz); flat index ix + nx * (iy + ny * iz)
    block:     aggregate box (bx, by, bz)
    transpose: False for P (n x nc), True for R = P^T (nc x n)
    """

    grid: Tuple[int, int, int]
    block: Tuple[int, int, int]
    transpose: bool

    @property
    def coarse(self) -> Tuple[int, int, int]:
        return tuple(-(-g // b) for g, b in zip(self.grid, self.block))


def blocked_map(grid, block) -> np.ndarray:
    """Fine -> coarse index of the blocked lexicographic aggregation:
    cell (ix, iy, iz) to (ix // bx, iy // by, iz // bz) on the coarse
    grid of ceil(n / b) cells per axis (int32)."""
    nx, ny, nz = grid
    bx, by, bz = block
    cx, cy = -(-nx // bx), -(-ny // by)
    i = np.arange(nx * ny * nz, dtype=np.int64)
    ix = i % nx
    iy = (i // nx) % ny
    iz = i // (nx * ny)
    return (ix // bx + cx * (iy // by + cy * (iz // bz))).astype(np.int32)


def detect_geo_transfer_np(row_offsets, col_indices, values, n_rows,
                           n_cols, grid, block):
    """``(GeoTransferMeta, weights)`` when the host CSR arrays are
    exactly the aggregation prolongation for ``(grid, block)`` or its
    transpose, else None.  ``weights`` is the fine-length vector of P's
    values (None when every value is 1).

    P must hold exactly one entry per row, in the column the blocked
    map gives; R must hold, row by row, the fine cells of each coarse
    cell in increasing order (sorted CSR of P^T).  Both are compared
    bit for bit: a reordered, reshuffled or otherwise built transfer
    keeps its general formats."""
    grid = tuple(int(g) for g in grid)
    block = tuple(int(b) for b in block)
    n = grid[0] * grid[1] * grid[2]
    meta = GeoTransferMeta(grid, block, False)
    nc = int(np.prod(meta.coarse))
    col_indices = np.asarray(col_indices)
    values = np.asarray(values)
    if values.ndim != 1 or col_indices.shape[0] != n or n == 0:
        return None
    agg = blocked_map(grid, block)
    row_offsets = np.asarray(row_offsets)
    if (n_rows, n_cols) == (n, nc):
        if not (
            np.array_equal(row_offsets, np.arange(n + 1))
            and np.array_equal(col_indices, agg)
        ):
            return None
        w = values
    elif (n_rows, n_cols) == (nc, n):
        counts = np.bincount(agg, minlength=nc)
        if not (
            row_offsets.shape[0] == nc + 1
            and row_offsets[0] == 0
            and np.array_equal(np.diff(row_offsets), counts)
        ):
            return None
        order = np.argsort(agg, kind="stable")
        if not np.array_equal(col_indices, order):
            return None
        w = np.empty_like(values)
        w[order] = values
        meta = meta._replace(transpose=True)
    else:
        return None
    if np.all(w == 1):
        w = None
    return meta, w


def _x_layout(nx: int, bx: int):
    """(chunk width, padded x extent) of the x step: one chunk when the
    (block-padded) row fits ``_X_CHUNK`` lanes, else whole chunks."""
    nxb = -(-nx // bx) * bx
    if nxb <= _X_CHUNK:
        return nxb, nxb
    kx = max(_X_CHUNK, bx)
    return kx, -(-nxb // kx) * kx


def _x_matrix(kx: int, bx: int, dtype) -> np.ndarray:
    """(kx, kx // bx) 0/1 matrix: entry (i, j) is 1 where i // bx == j."""
    return (
        np.arange(kx)[:, None] // bx == np.arange(kx // bx)[None, :]
    ).astype(dtype)


def _sum_x(u, nx, bx):
    """(m, nx) -> (m, ceil(nx / bx)): sums of bx consecutive columns."""
    if bx == 1:
        return u
    m = u.shape[0]
    cx = -(-nx // bx)
    kx, nxq = _x_layout(nx, bx)
    if nxq != nx:
        u = jnp.pad(u, ((0, 0), (0, nxq - nx)))
    s = jnp.asarray(_x_matrix(kx, bx, u.dtype))
    v = jnp.matmul(u.reshape(m, nxq // kx, kx), s,
                   precision=lax.Precision.HIGHEST)
    v = v.reshape(m, nxq // bx)
    return v if nxq // bx == cx else v[:, :cx]


def _expand_x(v, nx, bx):
    """(m, ceil(nx / bx)) -> (m, nx): each column repeated bx times."""
    if bx == 1:
        return v
    m, cx = v.shape
    kx, nxq = _x_layout(nx, bx)
    if nxq // bx != cx:
        v = jnp.pad(v, ((0, 0), (0, nxq // bx - cx)))
    s = jnp.asarray(_x_matrix(kx, bx, v.dtype).T)
    u = jnp.matmul(v.reshape(m, nxq // kx, kx // bx), s,
                   precision=lax.Precision.HIGHEST)
    u = u.reshape(m, nxq)
    return u if nxq == nx else u[:, :nx]


def _sum_blocks(u, b):
    """(m, b * L) -> (m, L): the sum of b side-by-side column blocks."""
    L = u.shape[1] // b
    out = u[:, :L]
    for k in range(1, b):
        out = out + u[:, k * L:(k + 1) * L]
    return out


def _tile_blocks(u, b):
    """(m, L) -> (m, b * L): b copies side by side."""
    return u if b == 1 else jnp.concatenate([u] * b, axis=1)


def geo_restrict(meta: GeoTransferMeta, x):
    """R @ x for R = P^T of unit weights: box sums of the fine vector."""
    nx, ny, nz = meta.grid
    bx, by, bz = meta.block
    cx, cy, cz = meta.coarse
    u = x.reshape(nz, ny * nx)
    if cy * by != ny:
        u = jnp.pad(u.reshape(nz, ny, nx),
                    ((0, 0), (0, cy * by - ny), (0, 0)))
    if cz * bz != nz:
        u = jnp.pad(u.reshape(nz, -1), ((0, cz * bz - nz), (0, 0)))
    u = _sum_blocks(u.reshape(cz, -1), bz)
    u = _sum_blocks(u.reshape(cz * cy, by * nx), by)
    return _sum_x(u, nx, bx).reshape(-1)


def geo_prolong(meta: GeoTransferMeta, xc):
    """P @ xc for unit weights: each coarse value copied over its box."""
    nx, ny, nz = meta.grid
    bx, by, bz = meta.block
    cx, cy, cz = meta.coarse
    u = _tile_blocks(_expand_x(xc.reshape(cz * cy, cx), nx, bx), by)
    u = _tile_blocks(u.reshape(cz, -1), bz).reshape(cz * bz, -1)[:nz]
    if cy * by != ny:
        u = u.reshape(nz, cy * by, nx)[:, :ny]
    return u.reshape(-1)


def geo_transfer_spmv(A, x):
    """SpMV of a matrix in the structured-transfer format: R @ x or
    P @ x, with P's fine-length weights applied on the fine side."""
    meta = A.geo_meta
    if meta.transpose:
        with jax.named_scope("geo_restrict"):
            if A.geo_w is not None:
                x = x * A.geo_w
            return geo_restrict(meta, x)
    with jax.named_scope("geo_prolong"):
        y = geo_prolong(meta, x)
        return y if A.geo_w is None else y * A.geo_w
