"""Host-side matrix partitioner (reference DistributedManager +
DistributedArranger, src/distributed/distributed_manager.cu:1040-1345
loadDistributedMatrix partition/renumber path, distributed_arranger.h
create_B2L/create_neighbors/create_boundary_lists).

Partitions a CSR matrix into N shards with owned-first local renumbering
and appended halo columns — the reference's local index layout.  All
per-shard arrays are padded to identical shapes and stacked along a
leading shard axis so the solve path runs under ``shard_map`` as one
static SPMD program.

Two partition shapes:
  * contiguous block rows (the reference's default partition vector)
  * px×py×pz grid slabs when the matrix is stencil-structured
    (AMGX_generate_distributed_poisson_7pt semantics, amgx_c.h:510-522)
    — owned rows of a shard are a lexicographic sub-box, so boundary
    (halo) size is O(surface), not O(volume).

Halo exchange contract (on-device, distributed/solve.py): each shard
gathers its boundary values into per-NEIGHBOR send buffers and the
exchange is one ``lax.ppermute`` per direction over ICI — comm volume
O(boundary).  Partitions whose halo graph is not a small neighbor set
fall back to the all_gather pool (comm O(N·max_send)).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import numpy as np
import scipy.sparse as sps

# Maximum distinct neighbor directions before falling back to the
# all_gather pool exchange (3D face-adjacency needs 6; diagonal-coupled
# 3D stencils on a 3D process grid need up to 26).
_MAX_DIRECTIONS = 26


@dataclasses.dataclass
class DistributedMatrix:
    """Stacked padded per-shard arrays (host numpy; moved to device by
    feeding into jitted/shard_mapped functions)."""

    n_global: int
    n_parts: int
    rows_per_part: int  # padded uniform local row count
    # ELL storage (local columns: 0..rows-1 owned, rows.. halo slots).
    # Block matrices (reference BSR, multiply.cu:49-71 bsrmv dispatch)
    # append the block dims: ell_vals [N, rows, w, b, b], diag
    # [N, rows, b, b], vectors [N, rows, b] — halo exchange and the
    # partition plan operate at BLOCK-row granularity (messages carry
    # b-vectors), as the reference's distributed manager does.
    ell_cols: np.ndarray  # [N, rows, w] int32
    ell_vals: np.ndarray  # [N, rows, w] or [N, rows, w, b, b]
    diag: np.ndarray  # [N, rows] or [N, rows, b, b]
    block_size: int = 1
    # --- neighbor (ppermute) exchange: per direction d ---
    # perms[d]: list[(src, dst)] device pairs; send_idx[d]: [N, ms_d]
    # local indices to pack; each shard's halo is filled from the
    # received buffers via (halo_dir, halo_pos).
    perms: Any = None  # tuple of tuples of (src, dst)
    send_idx_d: Any = None  # tuple of [N, ms_d] int32
    halo_dir: Optional[np.ndarray] = None  # [N, max_halo] int32 (dir id)
    halo_pos: Optional[np.ndarray] = None  # [N, max_halo] int32
    # --- all_gather fallback exchange ---
    send_idx: Optional[np.ndarray] = None  # [N, max_send] int32
    halo_src_part: Optional[np.ndarray] = None  # [N, max_halo] int32
    halo_src_pos: Optional[np.ndarray] = None  # [N, max_halo] int32
    max_send: int = 0
    max_halo: int = 0
    # interior/boundary split (latency hiding, reference
    # multiply.cu:95-110): interior rows reference no halo columns, so
    # their partial product depends only on x_loc and overlaps with the
    # in-flight halo exchange.  Row masks only — the SpMV applies them
    # to the shared ELL arrays (no second operator copy, no scatter).
    int_mask: Optional[np.ndarray] = None  # [N, rows] bool
    own_mask: Optional[np.ndarray] = None  # [N, rows] bool (non-pad)
    # compacted boundary row list [N, max_nb] (pad -> rows, the spill
    # slot): the boundary pass gathers/computes/scatter-adds ONLY these
    # O(surface) rows, which (a) avoids the masked full-size second
    # pass and (b) keeps the interior partial product in a fusion with
    # NO dependence on the halo permutes, so XLA's latency-hiding
    # scheduler can overlap it with the exchange
    # (ci/check_overlap_hlo.py asserts this on the compiled HLO)
    bnd_rows: Optional[np.ndarray] = None  # [N, max_nb] int32
    # windowed-tiled ELL arrays of the INTERIOR rows (ops.pallas_well
    # layout, stacked on the shard axis): the interior pass reads only
    # x_loc, so on TPU it rides the Pallas windowed kernel while the
    # halo exchange is in flight; boundary rows stay on the XLA path.
    ell_wcols: Optional[np.ndarray] = None  # [N, nt, 8, w*128] int32
    ell_wvals: Optional[np.ndarray] = None  # [N, nt, 8, w*128]
    ell_wbase: Optional[np.ndarray] = None  # [N, nt] int32
    ell_wrows: Optional[np.ndarray] = None  # [N, nt, 1, K] int32
    ell_wcount: Optional[np.ndarray] = None  # [N, nt] int32
    ell_wwidth: Optional[int] = None  # window lanes (static)
    # row ownership: owner[i] = part owning global row i;
    # local_of[i] = its local slot — identity layout for contiguous
    # partitions (owner = i // rows_per_part).
    owner: Optional[np.ndarray] = None
    local_of: Optional[np.ndarray] = None
    # number of real (non-padding) owned rows per shard
    n_owned: Optional[np.ndarray] = None
    # process grid (px, py, pz) when the slab partition was used
    proc_grid: Any = None
    # per-shard sparsity keys: the LOCALIZED pattern of each shard
    # hashed through core.matrix.sparsity_fingerprint — the same
    # content hash the serve HierarchyCache/ArtifactStore key on, so a
    # sharded hierarchy is cache-addressable exactly like a
    # single-device one (no ad-hoc hash; stable across processes)
    shard_fps: Any = None

    @property
    def uses_ppermute(self) -> bool:
        return self.perms is not None

    @property
    def fingerprint(self) -> Optional[str]:
        """Content hash of the WHOLE partitioned pattern: the shard
        fingerprints plus the layout metadata that changes the traced
        program (part count, padded rows, block size).  Two uploads of
        the same global pattern under the same partition collide; a
        different shard count is a different program and keys apart."""
        if self.shard_fps is None:
            return None
        import hashlib

        h = hashlib.blake2b(digest_size=16)
        h.update(
            np.asarray(
                [self.n_global, self.n_parts, self.rows_per_part,
                 self.block_size],
                dtype=np.int64,
            ).tobytes()
        )
        for fp in self.shard_fps:
            h.update(str(fp).encode())
        return h.hexdigest()

    def halo_stats(self) -> dict:
        """Halo-map anatomy for telemetry and the ci gates: per-shard
        ghost-row counts, the exchange mode, neighbor-direction count,
        and the analytic bytes one halo exchange moves (the same model
        DistributedAMG.collective_stats uses per level)."""
        item = np.dtype(
            self.ell_vals.dtype
            if hasattr(self.ell_vals, "dtype") else np.float64
        ).itemsize
        bvec = max(int(self.block_size), 1)
        ghost = None
        if isinstance(self.ell_cols, np.ndarray):
            rows_pp = self.rows_per_part
            ghost = [
                int(np.unique(
                    self.ell_cols[p][self.ell_cols[p] >= rows_pp]
                ).size)
                for p in range(self.n_parts)
            ]
        if self.uses_ppermute:
            mode = "ppermute"
            directions = len(self.perms)
            exchange_bytes = sum(
                len(self.perms[d]) * int(np.asarray(s).shape[-1])
                for d, s in enumerate(self.send_idx_d)
            ) * item * bvec
        else:
            mode = "allgather"
            directions = 0
            exchange_bytes = (
                self.n_parts * int(self.max_send) * item * bvec
            )
        return dict(
            mode=mode,
            directions=directions,
            ghost_rows=ghost,
            ghost_rows_total=(
                int(sum(ghost)) if ghost is not None else None
            ),
            max_halo=int(self.max_halo),
            exchange_bytes=int(exchange_bytes),
        )

    def pad_vector(self, v):
        """Global vector (n_global*b,) -> stacked padded [N, rows[, b]].

        ``owner is None`` means contiguous-by-offset ownership (the
        per-process layout): part p owns global rows
        [offs[p], offs[p+1]) with offs = cumsum(n_owned) — correct for
        non-uniform blocks too, unlike a flat reshape."""
        b = self.block_size
        v = np.asarray(v)
        if b > 1:
            v = v.reshape(-1, b)
            out = np.zeros(
                (self.n_parts, self.rows_per_part, b), dtype=v.dtype
            )
        else:
            out = np.zeros(
                (self.n_parts, self.rows_per_part), dtype=v.dtype
            )
        if self.owner is None:
            offs = np.concatenate(
                [[0], np.cumsum(self.n_owned)]
            ).astype(np.int64)
            for p in range(self.n_parts):
                out[p, : self.n_owned[p]] = v[offs[p]: offs[p + 1]]
        else:
            out[self.owner, self.local_of] = v
        return out

    def unpad_vector(self, vp):
        vp = np.asarray(vp)
        if self.owner is None:
            flat = np.concatenate(
                [vp[p, : self.n_owned[p]] for p in range(self.n_parts)]
            )
        else:
            flat = vp[self.owner, self.local_of]
        return flat.reshape(-1) if self.block_size > 1 else flat


def pack_boundary_rows(rows_by_part, rows_pp, max_nb=None):
    """Stack per-part boundary-row index lists as [N, max_nb] int32,
    padding with the spill slot ``rows_pp`` (the boundary scatter-add
    targets a length rows_pp+1 buffer whose last slot is discarded)."""
    if max_nb is None:
        max_nb = max((len(r) for r in rows_by_part), default=0)
    max_nb = max(int(max_nb), 1)
    out = np.full((len(rows_by_part), max_nb), rows_pp, dtype=np.int32)
    for p, r in enumerate(rows_by_part):
        out[p, : len(r)] = r
    return out


def part_interior_windowed(
    part, ell_cols_p, ell_vals_p, int_mask_p, rows_pp, count
):
    """Windowed tiling (ops.pallas_well layout) of ONE shard's interior
    rows, or None when its interior columns have no bounded window.
    Interior columns are all local (< rows_pp), so the kernel gathers
    from x_loc only — it runs while the halo exchange is in flight."""
    from amgx_tpu.ops.pallas_well import build_windowed_ell

    m = int_mask_p[:, None]
    cols_p = np.where(m, ell_cols_p, 0)
    vals_p = np.where(m, ell_vals_p, 0)
    lens = np.zeros(rows_pp, dtype=np.int64)
    lens[: int(count)] = np.diff(part["indptr"])
    lens[~int_mask_p] = 0  # boundary/padding rows: no real slots
    ro = np.concatenate([[0], np.cumsum(lens)])
    return build_windowed_ell(ro, cols_p, vals_p)


def _build_interior_windowed(
    parts, ell_cols, ell_vals, int_mask, rows_pp, counts
):
    """Per-shard windowed tiling stacked on the shard axis (one
    :class:`~amgx_tpu.ops.pallas_well.WindowedEll`), or None when any
    shard's interior declines the kernel."""
    per = []
    for p in range(ell_cols.shape[0]):
        built = part_interior_windowed(
            parts[p], ell_cols[p], ell_vals[p], int_mask[p], rows_pp,
            counts[p],
        )
        if built is None:
            return None
        per.append(built)
    from amgx_tpu.ops.pallas_well import WindowedEll

    k = max(b.rows.shape[2] for b in per)
    return WindowedEll(
        cols=np.stack([b.cols for b in per]),
        vals=np.stack([b.vals for b in per]),
        bases=np.stack([b.bases for b in per]),
        width=max(b.width for b in per),
        rows=np.stack([pad_touched_rows(b.rows, k) for b in per]),
        counts=np.stack([b.counts for b in per]),
    )


def pad_touched_rows(rows, k):
    """A shard's ``(nt, 1, K)`` touched-row lists padded to ``k``
    entries, the static length every shard shares (the kernel walks
    only each tile's count)."""
    return np.pad(rows, ((0, 0), (0, 0), (0, k - rows.shape[2])))


def tiled_ell_wanted(dtype) -> bool:
    """Whether to build windowed-tiled ELL copies for this matrix
    dtype — judged on the EFFECTIVE device dtype (f64 host arrays land
    as f32 on device when x64 is disabled, the usual TPU setting).
    Single gate for BOTH assembly paths (global partitioner and the
    multi-host one), so they cannot diverge."""
    import jax as _jax

    from amgx_tpu.core.matrix import _want_tiled_ell

    eff = np.dtype(dtype)
    if eff == np.float64 and not _jax.config.jax_enable_x64:
        eff = np.dtype(np.float32)
    return _want_tiled_ell(eff)


def part_ell_arrays(part, rows_pp, w, dtype):
    """One shard's padded ELL block + diagonal — the per-shard slice of
    the stacked arrays (bit-parity-critical: both assembly paths, the
    global partitioner and the multi-host one, fill through here).
    Block parts (``vals`` of shape (nnzb, b, b)) produce block ELL
    arrays (rows, w, b, b) and block diagonals (rows, b, b)."""
    indptr, cols, vals = part["indptr"], part["cols"], part["vals"]
    vals = np.asarray(vals)
    nr = indptr.shape[0] - 1
    bshape = vals.shape[1:]  # () scalar, (b, b) block
    ell_cols = np.zeros((rows_pp, w), dtype=np.int32)
    ell_vals = np.zeros((rows_pp, w) + bshape, dtype=dtype)
    # padding rows get unit diagonal so smoothers stay finite there
    if bshape:
        diag = np.broadcast_to(
            np.eye(bshape[0], dtype=dtype), (rows_pp,) + bshape
        ).copy()
    else:
        diag = np.ones((rows_pp,), dtype=dtype)
    diag[:nr] = 0.0
    lens = np.diff(indptr)
    row_ids = np.repeat(np.arange(nr), lens)
    pos = np.arange(cols.shape[0]) - indptr[row_ids].astype(np.int64)
    ell_cols[row_ids, pos] = cols
    ell_vals[row_ids, pos] = vals
    dmask = cols == row_ids
    diag[row_ids[dmask]] = vals[dmask]
    return ell_cols, ell_vals, diag


def grid_partition_parts(grid, n_parts):
    """Choose a process grid px*py*pz == n_parts matching the domain
    aspect (largest domain axis gets the most parts)."""
    nx, ny, nz = grid

    def factorizations(n):
        out = []
        for px in range(1, n + 1):
            if n % px:
                continue
            m = n // px
            for py in range(1, m + 1):
                if m % py:
                    continue
                out.append((px, py, m // py))
        return out

    best, best_cost = None, None
    for px, py, pz in factorizations(n_parts):
        if px > nx or py > ny or pz > nz:
            continue
        # surface-to-volume proxy: total boundary area
        sx, sy, sz = nx / px, ny / py, nz / pz
        cost = (px > 1) * sy * sz + (py > 1) * sx * sz + (pz > 1) * sx * sy
        if best is None or cost < best_cost:
            best, best_cost = (px, py, pz), cost
    return best


def partition_rows(n, n_parts, grid=None, proc_grid=None):
    """owner[i] for each global row.  Contiguous blocks by default;
    grid slabs when (nx, ny, nz) geometry is provided."""
    if grid is None:
        rows_pp = -(-n // n_parts)
        return np.minimum(
            np.arange(n, dtype=np.int64) // rows_pp, n_parts - 1
        ).astype(np.int32), None
    nx, ny, nz = grid
    if proc_grid is None:
        proc_grid = grid_partition_parts(grid, n_parts)
    if proc_grid is None:
        rows_pp = -(-n // n_parts)
        return np.minimum(
            np.arange(n, dtype=np.int64) // rows_pp, n_parts - 1
        ).astype(np.int32), None
    px, py, pz = proc_grid
    i = np.arange(n, dtype=np.int64)
    ix, iy, iz = i % nx, (i // nx) % ny, i // (nx * ny)
    # balanced slab boundaries
    bx = np.minimum(ix * px // nx, px - 1)
    by = np.minimum(iy * py // ny, py - 1)
    bz = np.minimum(iz * pz // nz, pz - 1)
    return (bx + px * (by + py * bz)).astype(np.int32), proc_grid


def gather_row_entries(indptr, rsel):
    """Entry ids of CSR rows ``rsel``, vectorized (repeat/cumsum — no
    per-row Python loop; this sits on the setup hot path)."""
    indptr = np.asarray(indptr, dtype=np.int64)
    rsel = np.asarray(rsel, dtype=np.int64)
    lens = (indptr[rsel + 1] - indptr[rsel]).astype(np.int64)
    total = int(lens.sum())
    if total == 0:
        return np.zeros(0, dtype=np.int64), lens
    starts = np.repeat(indptr[rsel], lens)
    offs = np.arange(total, dtype=np.int64) - np.repeat(
        np.concatenate([[0], np.cumsum(lens)[:-1]]), lens
    )
    return starts + offs, lens


def block_csr_arrays(Asp, block_size):
    """Scalar CSR (n*b square) -> block-row CSR arrays
    (indptr, block col indices, values (nnzb, b, b)) — the host-side
    BSR view the block partition consumes (reference block-CSR
    matrix.h:65 layout)."""
    b = int(block_size)
    bsr = sps.bsr_matrix(Asp.tocsr(), blocksize=(b, b))
    bsr.sort_indices()
    return (
        bsr.indptr.astype(np.int64),
        bsr.indices.astype(np.int64),
        np.asarray(bsr.data),
    )


def partition_matrix(
    Asp: sps.csr_matrix,
    n_parts: int,
    grid=None,
    proc_grid=None,
    owner=None,
    block_size: int = 1,
) -> DistributedMatrix:
    """Partition + owned-first renumber + halo/exchange maps.

    ``grid``/``proc_grid`` opt into the px×py×pz slab partition;
    ``owner`` supplies an arbitrary precomputed partition vector
    (reference partition-vector upload path).  ``block_size`` b > 1
    partitions at BLOCK-row granularity (reference distributed block
    path): ``Asp`` is the scalar (n*b square) matrix, ``owner``/
    ``grid`` describe block rows, and the device arrays carry b×b
    blocks.
    """
    if block_size > 1:
        indptr, bcols, bvals = block_csr_arrays(Asp, block_size)
        n = indptr.shape[0] - 1
        if owner is None:
            owner, proc_grid = partition_rows(
                n, n_parts, grid, proc_grid
            )
        else:
            owner = np.asarray(owner, dtype=np.int32)
        local_of, counts, part_rows = local_numbering(owner, n_parts)
        rows_pp = max(int(counts.max()), 1)
        parts = []
        for p in range(n_parts):
            ent, lens = gather_row_entries(indptr, part_rows[p])
            lptr = np.concatenate([[0], np.cumsum(lens)]).astype(
                np.int64
            )
            parts.append(
                localize_columns(
                    lptr, bcols[ent], bvals[ent], owner,
                    local_of, p, rows_pp,
                )
            )
        return finalize_partition(
            parts, owner, local_of, counts, n, n_parts, proc_grid
        )
    n = Asp.shape[0]
    Asp = Asp.tocsr()
    Asp.sort_indices()
    if owner is None:
        owner, proc_grid = partition_rows(n, n_parts, grid, proc_grid)
    else:
        owner = np.asarray(owner, dtype=np.int32)

    local_of, counts, part_rows = local_numbering(owner, n_parts)
    rows_pp = max(int(counts.max()), 1)
    parts = []
    for p in range(n_parts):
        local = Asp[part_rows[p]].tocsr()
        parts.append(
            localize_columns(
                local.indptr, local.indices, local.data, owner,
                local_of, p, rows_pp,
            )
        )
    return finalize_partition(
        parts, owner, local_of, counts, n, n_parts, proc_grid
    )


class Ownership:
    """Analytic row-ownership with O(n_parts) state (the per-process
    memory contract: no global-length arrays).  ``owner_of``/
    ``local_of_ids`` map global-id arrays; ``global_rows(p)`` lists one
    part's owned global ids (O(local)); ``materialize()`` builds the
    O(n_global) arrays — only for boundary conveniences on SMALL levels
    (the consolidated tail)."""

    counts: np.ndarray

    @property
    def n_parts(self) -> int:
        return int(self.counts.shape[0])

    @property
    def n_global(self) -> int:
        return int(self.counts.sum())

    def owner_of(self, ids):
        raise NotImplementedError

    def local_of_ids(self, ids):
        raise NotImplementedError

    def global_rows(self, p):
        raise NotImplementedError

    def materialize(self):
        owner = np.empty(self.n_global, dtype=np.int32)
        local_of = np.empty(self.n_global, dtype=np.int32)
        for p in range(self.n_parts):
            g = self.global_rows(p)
            owner[g] = p
            local_of[g] = np.arange(len(g), dtype=np.int32)
        return owner, local_of

    @property
    def uniform_contiguous(self) -> bool:
        return False

    @property
    def offset_blocks(self) -> bool:
        """True when part p owns exactly global rows
        [cumsum(counts)[p], cumsum(counts)[p+1]) — the layout the
        owner=None pad/unpad convention assumes."""
        return False


class OffsetOwnership(Ownership):
    """Contiguous row blocks given by part offsets (the reference's
    partition-offsets upload path, sharded_partition's shape)."""

    def __init__(self, part_offsets):
        self.part_offsets = np.asarray(part_offsets, dtype=np.int64)
        self.counts = (
            self.part_offsets[1:] - self.part_offsets[:-1]
        ).astype(np.int64)

    def owner_of(self, ids):
        return (
            np.searchsorted(
                self.part_offsets, np.asarray(ids), side="right"
            )
            - 1
        ).astype(np.int32)

    def local_of_ids(self, ids):
        ids = np.asarray(ids, dtype=np.int64)
        return (
            ids - self.part_offsets[self.owner_of(ids)]
        ).astype(np.int32)

    def global_rows(self, p):
        return np.arange(
            self.part_offsets[p], self.part_offsets[p + 1],
            dtype=np.int64,
        )

    @property
    def uniform_contiguous(self) -> bool:
        rows_pp = int(self.counts.max()) if len(self.counts) else 1
        expect = np.minimum(
            np.arange(len(self.part_offsets)) * rows_pp,
            self.part_offsets[-1],
        )
        return bool(np.array_equal(self.part_offsets, expect))

    @property
    def offset_blocks(self) -> bool:
        return True


class GridOwnership(Ownership):
    """px*py*pz slab partition of an nx*ny*nz lexicographic grid —
    ownership is computed from coordinates (O(1) state), halo size is
    O(surface).  Matches partition_rows(grid=...) numbering."""

    def __init__(self, grid, proc_grid):
        self.grid = tuple(int(v) for v in grid)
        self.proc_grid = tuple(int(v) for v in proc_grid)
        nx, ny, nz = self.grid
        px, py, pz = self.proc_grid
        # slab boundaries identical to partition_rows
        self._xb = np.searchsorted(
            np.minimum(np.arange(nx) * px // nx, px - 1),
            np.arange(px + 1), side="left",
        )
        self._yb = np.searchsorted(
            np.minimum(np.arange(ny) * py // ny, py - 1),
            np.arange(py + 1), side="left",
        )
        self._zb = np.searchsorted(
            np.minimum(np.arange(nz) * pz // nz, pz - 1),
            np.arange(pz + 1), side="left",
        )
        cx = np.diff(self._xb)
        cy = np.diff(self._yb)
        cz = np.diff(self._zb)
        self.counts = (
            cx[None, None, :] * cy[None, :, None] * cz[:, None, None]
        ).reshape(-1).astype(np.int64)

    def _coords(self, ids):
        nx, ny, _ = self.grid
        ids = np.asarray(ids, dtype=np.int64)
        return ids % nx, (ids // nx) % ny, ids // (nx * ny)

    def owner_of(self, ids):
        nx, ny, nz = self.grid
        px, py, pz = self.proc_grid
        ix, iy, iz = self._coords(ids)
        bx = np.minimum(ix * px // nx, px - 1)
        by = np.minimum(iy * py // ny, py - 1)
        bz = np.minimum(iz * pz // nz, pz - 1)
        return (bx + px * (by + py * bz)).astype(np.int32)

    def local_of_ids(self, ids):
        nx, ny, nz = self.grid
        px, py, pz = self.proc_grid
        ix, iy, iz = self._coords(ids)
        bx = np.minimum(ix * px // nx, px - 1)
        by = np.minimum(iy * py // ny, py - 1)
        bz = np.minimum(iz * pz // nz, pz - 1)
        ox, oy, oz = self._xb[bx], self._yb[by], self._zb[bz]
        sx = self._xb[bx + 1] - ox
        sy = self._yb[by + 1] - oy
        # local slot = lexicographic index within the owned sub-box
        # (matches local_numbering's global-order-preserving numbering)
        return (
            (ix - ox) + sx * ((iy - oy) + sy * (iz - oz))
        ).astype(np.int32)

    def global_rows(self, p):
        nx, ny, _ = self.grid
        px, py, _ = self.proc_grid
        bx = p % px
        by = (p // px) % py
        bz = p // (px * py)
        xs = np.arange(self._xb[bx], self._xb[bx + 1], dtype=np.int64)
        ys = np.arange(self._yb[by], self._yb[by + 1], dtype=np.int64)
        zs = np.arange(self._zb[bz], self._zb[bz + 1], dtype=np.int64)
        return (
            xs[None, None, :]
            + nx * (ys[None, :, None] + self.grid[1] * zs[:, None, None])
        ).reshape(-1)


class ArrayOwnership(Ownership):
    """Ownership from explicit owner/local_of arrays (the reference's
    arbitrary partition-vector upload).  O(n_global) state — the
    single-process compatibility shape, not the multi-host one."""

    def __init__(self, owner, local_of=None, n_parts=None):
        self.owner = np.asarray(owner, dtype=np.int32)
        n_parts = (
            int(self.owner.max()) + 1 if n_parts is None else n_parts
        )
        self.counts = np.bincount(
            self.owner, minlength=n_parts
        ).astype(np.int64)
        if local_of is None:
            local_of, _, self._part_rows = local_numbering(
                self.owner, n_parts
            )
        else:
            self._part_rows = None
        self.local_arr = np.asarray(local_of, dtype=np.int32)

    def owner_of(self, ids):
        return self.owner[np.asarray(ids)]

    def local_of_ids(self, ids):
        return self.local_arr[np.asarray(ids)]

    def global_rows(self, p):
        if self._part_rows is not None:
            return self._part_rows[p]
        return np.nonzero(self.owner == p)[0]

    def materialize(self):
        return self.owner, self.local_arr


def local_numbering(owner, n_parts):
    """(local_of, counts, part_rows): slot of each global row within its
    part (global order preserved within a part), owned-row counts, and
    the global row list per part."""
    n = owner.shape[0]
    order = np.argsort(owner, kind="stable")
    counts = np.bincount(owner, minlength=n_parts)
    local_of = np.zeros(n, dtype=np.int32)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    pos_in_part = np.arange(n, dtype=np.int64) - starts[owner[order]]
    local_of[order] = pos_in_part.astype(np.int32)
    part_rows = [order[starts[p]: starts[p] + counts[p]]
                 for p in range(n_parts)]
    return local_of, counts, part_rows


def halo_localize(gcols, is_owned, owned_local, rows_pp):
    """Shared halo-slot numbering (bit-parity critical: the multi-host
    per-process path must reproduce this exactly): off-owned columns
    map to ``rows_pp + position in the SORTED unique halo-id list``."""
    halo_glob = np.unique(gcols[~is_owned])
    cols = np.empty(gcols.shape, dtype=np.int32)
    cols[is_owned] = owned_local
    if halo_glob.size:
        cols[~is_owned] = (
            rows_pp + np.searchsorted(halo_glob, gcols[~is_owned])
        ).astype(np.int32)
    return cols, halo_glob


def localize_columns(indptr, gcols, vals, owner, local_of, p, rows_pp):
    """Owned-first renumbering of one shard's rows: owned columns map to
    their local slot, off-shard columns to appended halo slots
    (reference loadDistributed_LocalToGlobal/InitLocalMatrix)."""
    is_owned = owner[gcols] == p
    cols, halo_glob = halo_localize(
        gcols, is_owned, local_of[gcols[is_owned]], rows_pp
    )
    return dict(indptr=indptr, cols=cols, vals=vals, halo_glob=halo_glob)


def build_exchange_plan(halo_globs, owner_fn, local_fn, n_parts):
    """Exchange plan from each part's sorted halo-id list alone.

    ``halo_globs[p]`` is part p's ``halo_glob`` (sorted global ids it
    needs); ``owner_fn``/``local_fn`` map global-id arrays to owning
    part / local slot.  Everything here is O(total boundary) — in a
    multi-host launch the lists ride one small allgather and every
    process builds the (replicated) plan independently
    (reference distributed_arranger.h create_B2L/create_boundary_lists).

    Returns ``(dm, fallback)``: the neighbor-ppermute plan dict (or
    None) and the all_gather fallback maps dict.
    """
    # boundary (B2L) lists: rows of p needed by q, sorted by global id
    send_sorted = {}  # (src_owner, dst) -> sorted global ids
    for p, hg in enumerate(halo_globs):
        hg = np.asarray(hg, dtype=np.int64)
        if hg.size == 0:
            continue
        owners = owner_fn(hg)
        for o in np.unique(owners):
            send_sorted[(int(o), p)] = hg[owners == o]

    max_halo = max((len(h) for h in halo_globs), default=0)
    max_halo = max(max_halo, 1)

    # ---- neighbor-direction (ppermute) plan -------------------------
    # direction = the shard-index displacement function; for grid slab
    # partitions every (src, dst) pair with traffic maps to one of a
    # small set of displacements, one ppermute each.
    pairs = sorted(send_sorted.keys())
    deltas = sorted({dst - src for (src, dst) in pairs})
    dm = None
    # no pairs at all (every column local on every part — e.g. a level
    # graded onto one shard) is a valid neighbor plan with zero
    # directions, NOT an all_gather fallback
    if len(deltas) <= _MAX_DIRECTIONS:
        perms, send_idx_d = [], []
        halo_dir = np.zeros((n_parts, max_halo), dtype=np.int32)
        halo_pos = np.zeros((n_parts, max_halo), dtype=np.int32)
        for d, delta in enumerate(deltas):
            dpairs = [(s, t) for (s, t) in pairs if t - s == delta]
            ms = max(len(send_sorted[k]) for k in dpairs)
            sidx = np.zeros((n_parts, ms), dtype=np.int32)
            for (s, t) in dpairs:
                ids = send_sorted[(s, t)]
                sidx[s, : len(ids)] = local_fn(ids)
            perms.append(tuple(dpairs))
            send_idx_d.append(sidx)
            for (s, t) in dpairs:
                ids = send_sorted[(s, t)]
                hg = np.asarray(halo_globs[t], dtype=np.int64)
                mine = np.isin(hg, ids)
                li = np.nonzero(mine)[0]
                halo_dir[t, li] = d
                halo_pos[t, li] = np.searchsorted(ids, hg[mine])
        dm = dict(
            perms=tuple(perms),
            send_idx_d=tuple(send_idx_d),
            halo_dir=halo_dir,
            halo_pos=halo_pos,
        )

    # ---- all_gather fallback maps (always built: small, and used by
    # setup-side consistency checks) ----------------------------------
    send_union = [np.array([], dtype=np.int64)] * n_parts
    for (s, t), ids in send_sorted.items():
        send_union[s] = np.union1d(send_union[s], ids)
    max_send = max(max((len(s) for s in send_union), default=0), 1)
    send_idx = np.zeros((n_parts, max_send), dtype=np.int32)
    halo_src_part = np.zeros((n_parts, max_halo), dtype=np.int32)
    halo_src_pos = np.zeros((n_parts, max_halo), dtype=np.int32)
    for p in range(n_parts):
        su = send_union[p]
        if len(su):
            send_idx[p, : len(su)] = local_fn(su)
        hg = np.asarray(halo_globs[p], dtype=np.int64)
        if hg.size:
            owners = owner_fn(hg)
            halo_src_part[p, : hg.size] = owners
            halo_src_pos[p, : hg.size] = [
                int(np.searchsorted(send_union[int(o)], g))
                for o, g in zip(owners, hg)
            ]
    fallback = dict(
        send_idx=send_idx,
        halo_src_part=halo_src_part,
        halo_src_pos=halo_src_pos,
        max_send=max_send,
        max_halo=max_halo,
    )
    return dm, fallback


def finalize_partition(
    parts, owner, local_of, counts, n, n_parts, proc_grid=None,
    split=True, owner_fn=None, local_fn=None,
):
    """Build the exchange plan + stacked device arrays from per-shard
    localized CSRs (the output of localize_columns).

    ``owner``/``local_of`` may be None when ``owner_fn``/``local_fn``
    provide analytic ownership (the per-process path: no global-length
    arrays; pad/unpad then require uniform contiguous blocks)."""
    rows_pp = max(int(counts.max()), 1)
    Adtype = parts[0]["vals"].dtype if parts else np.float64
    bshape = np.asarray(parts[0]["vals"]).shape[1:] if parts else ()
    block_size = bshape[0] if bshape else 1

    # per-shard pattern keys through the serve cache's content hash
    # (core.matrix.sparsity_fingerprint) — computed here, where the
    # localized CSR indices still exist, so sharded hierarchies key
    # the HierarchyCache/ArtifactStore without an ad-hoc hash
    from amgx_tpu.core.matrix import sparsity_fingerprint

    shard_fps = tuple(
        sparsity_fingerprint(
            part["indptr"],
            part["cols"],
            part["indptr"].shape[0] - 1,
            rows_pp + len(part["halo_glob"]),
            block_size,
        )
        for part in parts
    )

    if owner_fn is None:
        owner_fn = lambda ids: owner[ids]
    if local_fn is None:
        local_fn = lambda ids: local_of[ids]
    dm, fb = build_exchange_plan(
        [p["halo_glob"] for p in parts],
        owner_fn,
        local_fn,
        n_parts,
    )
    max_send, max_halo = fb["max_send"], fb["max_halo"]
    send_idx = fb["send_idx"]
    halo_src_part = fb["halo_src_part"]
    halo_src_pos = fb["halo_src_pos"]

    # ---- ELL with uniform width across shards -----------------------
    w = 1
    for part in parts:
        lens = np.diff(part["indptr"])
        if lens.size:
            w = max(w, int(lens.max()))
    ell_cols = np.zeros((n_parts, rows_pp, w), dtype=np.int32)
    ell_vals = np.zeros((n_parts, rows_pp, w) + bshape, dtype=Adtype)
    diag = np.zeros((n_parts, rows_pp) + bshape, dtype=Adtype)
    for p, part in enumerate(parts):
        ell_cols[p], ell_vals[p], diag[p] = part_ell_arrays(
            part, rows_pp, w, Adtype
        )

    # ---- interior/boundary split masks (latency hiding) -------------
    # rows whose every stored column is local (< rows_pp) are interior
    int_mask = own_mask = bnd_rows = None
    if split:
        is_bnd = (ell_cols >= rows_pp).any(axis=2)  # [N, rows]
        own_mask = np.zeros((n_parts, rows_pp), dtype=bool)
        for p in range(n_parts):
            own_mask[p, : counts[p]] = True
        int_mask = own_mask & ~is_bnd
        bnd_rows = pack_boundary_rows(
            [np.nonzero(own_mask[p] & is_bnd[p])[0]
             for p in range(n_parts)],
            rows_pp,
        )

    # ---- Pallas windowed tiling of the interior rows (TPU) ----------
    wt = None
    if (
        int_mask is not None
        and block_size == 1
        and tiled_ell_wanted(Adtype)
    ):
        wt = _build_interior_windowed(
            parts, ell_cols, ell_vals, int_mask, rows_pp, counts
        )

    return DistributedMatrix(
        n_global=n,
        n_parts=n_parts,
        rows_per_part=rows_pp,
        ell_cols=ell_cols,
        ell_vals=ell_vals,
        diag=diag,
        block_size=block_size,
        int_mask=int_mask,
        own_mask=own_mask,
        bnd_rows=bnd_rows,
        ell_wcols=None if wt is None else wt.cols,
        ell_wvals=None if wt is None else wt.vals,
        ell_wbase=None if wt is None else wt.bases,
        ell_wrows=None if wt is None else wt.rows,
        ell_wcount=None if wt is None else wt.counts,
        ell_wwidth=None if wt is None else wt.width,
        perms=None if dm is None else dm["perms"],
        send_idx_d=None if dm is None else dm["send_idx_d"],
        halo_dir=None if dm is None else dm["halo_dir"],
        halo_pos=None if dm is None else dm["halo_pos"],
        send_idx=send_idx,
        halo_src_part=halo_src_part,
        halo_src_pos=halo_src_pos,
        max_send=max_send,
        max_halo=max_halo,
        owner=owner,
        local_of=local_of,
        n_owned=counts.astype(np.int32),
        proc_grid=proc_grid,
        shard_fps=shard_fps,
    )
