"""Aggregation-AMG coarsening (reference src/aggregation/**, 11.4k LoC).

Selectors SIZE_2/SIZE_4/SIZE_8 are pairwise-matching passes (the reference
composes size-4/8 from repeated pairwise phases, size2_selector.cu /
size8_selector.cu); MULTI_PAIRWISE generalizes to ``aggregation_passes``.
Setup is host-side numpy/scipy (data-dependent shapes — the solve path
never sees it); the deterministic greedy matching corresponds to the
reference's determinism_flag=1 path.

Edge weights (weight_formula 0, core.cu registration):
    w_ij = 0.5*(|a_ij| + |a_ji|) / max(|a_ii|, |a_jj|)
Prolongation is the binary aggregate map; R = P^T; A_c = R A P
(coarse generators LOW_DEG/THRUST/HYBRID differ only in GPU kernel
strategy — one scipy product here).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import scipy.sparse as sps

from amgx_tpu.core.profiling import (
    setup_download,
    setup_phase,
    setup_upload,
)


def edge_weights(Asp: sps.csr_matrix, formula: int = 0) -> sps.csr_matrix:
    """Symmetric positive weight graph (zero diagonal)."""
    n = Asp.shape[0]
    absA = abs(Asp)
    d = np.abs(Asp.diagonal())
    d = np.where(d > 0, d, 1.0)
    if formula == 1:
        # w_ij = -0.5*(a_ij/a_ii + a_ji/a_jj)
        Dinv = sps.diags_array(1.0 / np.where(Asp.diagonal() != 0,
                                              Asp.diagonal(), 1.0))
        W = -(Dinv @ Asp + (Dinv @ Asp).T) * 0.5
        W = W.tocsr()
        W.data = np.maximum(W.data, 0.0)
    else:
        S = (absA + absA.T) * 0.5
        # divide each w_ij by max(d_i, d_j): do it entrywise
        S = S.tocoo()
        denom = np.maximum(d[S.row], d[S.col])
        W = sps.csr_matrix(
            (S.data / denom, (S.row, S.col)), shape=(n, n)
        )
    W.setdiag(0.0)
    W.eliminate_zeros()
    W.sort_indices()
    return W


def _first_per_row(rows_sorted, n):
    """Index of the first occurrence of each row id in a row-sorted array;
    -1 for absent rows."""
    first = np.full(n, -1, dtype=np.int64)
    # the input is row-sorted, so first occurrences are exactly the
    # boundary positions — an O(nnz) flag diff, not a unique sort
    if rows_sorted.shape[0]:
        mask = np.empty(rows_sorted.shape[0], dtype=bool)
        mask[0] = True
        np.not_equal(rows_sorted[1:], rows_sorted[:-1], out=mask[1:])
        idx = np.nonzero(mask)[0]
        first[rows_sorted[idx]] = idx
    return first


def pairwise_match(W: sps.csr_matrix, merge_singletons: bool = True,
                   max_rounds: int = 15,
                   max_unassigned: float = 0.0):
    """Deterministic pairwise matching via mutual-strongest-neighbour
    rounds (the handshaking scheme of the reference's size2 selector,
    fully vectorized; max_rounds mirrors max_matching_iterations and
    ``max_unassigned`` the max_unassigned_percentage early exit,
    size2_selector.cu:621-625).

    Returns agg (n,) int32 aggregate ids 0..n_agg-1.
    """
    n = W.shape[0]
    coo = W.tocoo()
    r, c, w = coo.row, coo.col, coo.data
    # per-row preference: heavy edges first; ties broken by a symmetric
    # per-edge hash (deterministic).  Without it, uniform-weight graphs
    # (Poisson) deadlock the handshake into chains — the reference breaks
    # ties with random edge weights for the same reason.
    jitter = _edge_jitter(r, c, n)
    order = np.lexsort((jitter, -w, r))
    rs, cs = r[order], c[order]

    partner = np.full(n, -1, dtype=np.int64)
    for _ in range(max_rounds):
        un = partner == -1
        if max_unassigned > 0 and un.mean() <= max_unassigned:
            break  # remaining rows join as merged singletons
        valid = un[rs] & un[cs]
        first = _first_per_row(rs[valid], n)
        # strongest available neighbour per unmatched vertex
        cand = np.full(n, -1, dtype=np.int64)
        has = first >= 0
        cand[has] = cs[valid][first[has]]
        # mutual handshake
        ok = (cand >= 0) & un
        idx = np.nonzero(ok)[0]
        mutual = idx[cand[cand[idx]] == idx]
        a = mutual[mutual < cand[mutual]]
        partner[a] = cand[a]
        partner[cand[a]] = a
        if a.size == 0:
            break

    # aggregate ids: pair root = min(i, partner); singletons own id
    root = np.where(partner >= 0, np.minimum(np.arange(n), partner),
                    np.arange(n))
    uniq, agg = np.unique(root, return_inverse=True)

    if merge_singletons:
        sizes = np.bincount(agg)
        is_single = sizes[agg] == 1
        if is_single.any():
            # strongest neighbour regardless of matching state
            first_all = _first_per_row(rs, n)
            best = np.full(n, -1, dtype=np.int64)
            hasn = first_all >= 0
            best[hasn] = cs[first_all[hasn]]
            move = is_single & (best >= 0)
            agg = agg.copy()
            agg[move] = agg[best[move]]
            uniq2, agg = np.unique(agg, return_inverse=True)
    return agg.astype(np.int32)


_DEVICE_MATCH_MAX_WIDTH = 32  # bounded-degree gate for the ELL matcher
_DEVICE_MATCH_MIN_ROWS = 16384  # below this, host numpy rounds win


def _device_matching_wanted() -> bool:
    """Backend gate for the XLA matcher: accelerators only.  On the
    CPU backend the "device" is the same cores the numpy rounds use,
    so the XLA handshake buys nothing at steady state and its first
    compile (~0.7-1.4 s measured on the CPU) dominates a cold setup."""
    return jax.default_backend() != "cpu"


def _edge_jitter(r, c, n):
    """Symmetric per-edge tie-break hash — the ONE definition both the
    host and device matchers key on (bit-parity contract)."""
    lo = np.minimum(r, c).astype(np.uint64)
    hi = np.maximum(r, c).astype(np.uint64)
    z = lo * np.uint64(n) + hi + np.uint64(0x9E3779B9)
    z = (z ^ (z >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
    z = (z ^ (z >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return (z ^ (z >> np.uint64(31))).astype(np.float64)


def _match_ell_arrays(W: sps.csr_matrix):
    """CSR -> padded ELL (cols, preference ranks) for the on-device
    matcher, or None when the row degree exceeds the ELL gate.

    Selection keys are PRE-RANKED on host: every edge gets its global
    position in the (weight desc, jitter asc) order — the host
    matcher's exact sort — as an int32, so the device rounds compare
    integers and the selections are bit-identical at ANY device float
    precision (x64 off on TPU must not change aggregates)."""
    n = W.shape[0]
    lens = np.diff(W.indptr)
    w = int(lens.max()) if lens.size else 0
    if w == 0 or w > _DEVICE_MATCH_MAX_WIDTH:
        return None
    if len(W.indices) > np.iinfo(np.int32).max:
        # int32 ranks would silently wrap at >= 2^31 edges and corrupt
        # selections (ADVICE r4 #1); the host matcher handles the
        # giant-graph case with int64 arithmetic
        return None
    r = np.repeat(np.arange(n, dtype=np.int64), lens)
    c = W.indices.astype(np.int64)
    jitter = _edge_jitter(r, c, n)
    order = np.lexsort((jitter, -W.data))
    rank = np.empty(len(c), dtype=np.int32)
    rank[order] = np.arange(len(c), dtype=np.int32)
    cols = np.full((n, w), n, dtype=np.int32)
    ranks = np.full((n, w), np.iinfo(np.int32).max, dtype=np.int32)
    pos = np.arange(len(c)) - W.indptr[r].astype(np.int64)
    cols[r, pos] = c
    ranks[r, pos] = rank
    return cols, ranks


@functools.partial(jax.jit, static_argnames=("max_rounds",))
def _device_match_rounds(cols, ranks, max_rounds):
    """Mutual-strongest-neighbour handshake rounds on device
    (reference size2_selector.cu matching kernels; XLA-compiled so on
    TPU the setup matching leaves the host).  Selection = minimum
    preference rank among available neighbours — integer compares,
    identical to the host matcher's (weight desc, jitter asc) pick at
    any device precision.  Returns (partner, best_all)."""
    n, w = cols.shape
    iota = jnp.arange(n)
    rmax = jnp.iinfo(jnp.int32).max

    def best_neighbour(valid):
        rv = jnp.where(valid, ranks, rmax)

        def slot(k, best):
            bc, br = best
            better = rv[:, k] < br
            return (
                jnp.where(better, cols[:, k], bc),
                jnp.where(better, rv[:, k], br),
            )

        bc, br = jax.lax.fori_loop(
            0, w, slot,
            (jnp.full((n,), -1, jnp.int32),
             jnp.full((n,), rmax, jnp.int32)),
        )
        return jnp.where(br < rmax, bc.astype(jnp.int64), -1)

    best_all = best_neighbour(jnp.ones(cols.shape, bool))

    def cond(state):
        partner, rounds, progress = state
        return (rounds < max_rounds) & progress

    def body(state):
        partner, rounds, _ = state
        un_ext = jnp.concatenate(
            [partner < 0, jnp.zeros((1,), bool)]
        )
        valid = un_ext[cols] & un_ext[:n][:, None]
        cand = best_neighbour(valid)
        ci = jnp.where(cand >= 0, cand, n)
        cand_ext = jnp.concatenate([cand, jnp.full((1,), -1, cand.dtype)])
        mutual = (cand >= 0) & (cand_ext[ci] == iota)
        a = mutual & (iota < cand)
        pext = jnp.concatenate(
            [partner, jnp.full((1,), -1, partner.dtype)]
        )
        # b-side writes land at partner[cand[a]]; non-a rows hit the
        # spill slot n (discarded)
        pext = pext.at[jnp.where(a, cand, n)].set(
            jnp.where(a, iota, -1)
        )
        partner = jnp.where(a, cand, pext[:n])
        return partner, rounds + 1, a.any()

    partner, _, _ = jax.lax.while_loop(
        cond, body,
        (jnp.full((n,), -1, jnp.int64), jnp.int32(0), jnp.bool_(True)),
    )
    return partner, best_all


def pairwise_match_device(W: sps.csr_matrix,
                          merge_singletons: bool = True,
                          max_rounds: int = 15):
    """On-device variant of :func:`pairwise_match` (VERDICT r3 #6:
    move the top setup offender on-device).  Falls back to the host
    matcher when the graph exceeds the bounded-degree ELL gate.
    Produces the same aggregates as the host matcher (asserted by
    tests) — selection keys are identical."""
    ell = _match_ell_arrays(W)
    if ell is None:
        return pairwise_match(W, merge_singletons, max_rounds)
    cols, ranks = ell
    partner, best_all = _device_match_rounds(
        jnp.asarray(cols), jnp.asarray(ranks), max_rounds
    )
    partner = np.asarray(partner)
    best_all = np.asarray(best_all)
    n = W.shape[0]
    root = np.where(
        partner >= 0, np.minimum(np.arange(n), partner), np.arange(n)
    )
    uniq, agg = np.unique(root, return_inverse=True)
    if merge_singletons:
        sizes = np.bincount(agg)
        is_single = sizes[agg] == 1
        if is_single.any():
            move = is_single & (best_all >= 0)
            agg = agg.copy()
            agg[move] = agg[best_all[move]]
            uniq2, agg = np.unique(agg, return_inverse=True)
    return agg.astype(np.int32)


def filter_edge_weights(W: sps.csr_matrix,
                        alpha: float) -> sps.csr_matrix:
    """Weak-edge filter (reference multi_pairwise.cu:931-945,
    filter_weights=1): drop edges with w_ij < alpha * max_k w_ik
    (symmetrized so the graph stays matchable both ways)."""
    coo = W.tocoo()
    rmax = np.zeros(W.shape[0])
    np.maximum.at(rmax, coo.row, coo.data)
    keep = (coo.data >= alpha * rmax[coo.row]) | (
        coo.data >= alpha * rmax[coo.col]
    )
    Wf = sps.csr_matrix(
        (np.where(keep, coo.data, 0.0), (coo.row, coo.col)),
        shape=W.shape,
    )
    Wf.eliminate_zeros()
    Wf.sort_indices()
    return Wf


def aggregate(Asp: sps.csr_matrix, passes: int, formula: int = 0,
              merge_singletons: bool = True, max_rounds: int = 15,
              filter_alpha: float = 0.0,
              serial_matching: bool = False,
              max_unassigned: float = 0.0) -> np.ndarray:
    """Compose `passes` pairwise matchings -> aggregates of size ~2^passes
    (reference SIZE_2=1, SIZE_4=2, SIZE_8=3 passes).  ``max_rounds``
    mirrors max_matching_iterations (size2_selector.cu:621);
    ``filter_alpha`` > 0 applies the filter_weights weak-edge filter;
    ``serial_matching`` forces the host matcher (multi_pairwise.cu
    serial_matching)."""
    n = Asp.shape[0]
    agg = np.arange(n, dtype=np.int32)
    W = edge_weights(Asp, formula)
    if filter_alpha > 0:
        W = filter_edge_weights(W, filter_alpha)
    for p in range(passes):
        # large bounded-degree graphs match on device (XLA handshake
        # rounds — bit-identical to the host matcher); small/ragged
        # graphs stay on host where the numpy rounds are cheaper than
        # a compile
        if (not serial_matching and max_unassigned <= 0
                and W.shape[0] >= _DEVICE_MATCH_MIN_ROWS
                and _device_matching_wanted()):
            sub = pairwise_match_device(W, merge_singletons,
                                        max_rounds=max_rounds)
        else:
            sub = pairwise_match(W, merge_singletons,
                                 max_rounds=max_rounds,
                                 max_unassigned=max_unassigned)
        agg = sub[agg]
        if p + 1 < passes:
            nc = int(sub.max()) + 1
            Pb = sps.csr_matrix(
                (np.ones(W.shape[0]), (np.arange(W.shape[0]), sub)),
                shape=(W.shape[0], nc),
            )
            W = (Pb.T @ W @ Pb).tocsr()
            W.setdiag(0.0)
            W.eliminate_zeros()
    return agg


SELECTOR_PASSES = {
    "SIZE_2": 1,
    "SIZE_4": 2,
    "SIZE_8": 3,
    "MULTI_PAIRWISE": None,  # uses aggregation_passes config
    "DUMMY": 1,
    "GEO": 3,
}


# ---------------------------------------------------------------------------
# Structured (geometric) aggregation — the TPU fast path.
#
# Reference parity: GEO selector (src/aggregation/selectors/geo_selector.cu)
# aggregates by spatial blocks using user-attached geometry.  Here the
# geometry is *inferred* from the stencil structure instead: a matrix whose
# distinct diagonals decompose as a + b*nx + c*nx*ny (a,b,c in {-1,0,1})
# is a <=27-point stencil on an (nx, ny, nz) grid.  Aggregating such
# matrices in 2x2x2 lexicographic blocks keeps EVERY Galerkin coarse
# operator a <=27-point stencil on the coarser grid, so the whole AMG
# hierarchy rides the DIA shift+FMA SpMV path (no TPU gathers at any
# level).  Irregular matching (the fallback below) destroys bandedness
# and forces coarse levels onto gather-bound formats.


def _col_diffs(Asp: sps.csr_matrix, dtype=np.int64):
    """col - row per stored entry, straight from CSR (no COO copy —
    this runs on every level of every setup).  ``dtype`` may be int32
    when both dimensions fit (the offset-scan unique sorts ~2x faster
    there) — entry ORDER is the contract axis_strengths relies on."""
    rows = np.repeat(
        np.arange(Asp.shape[0], dtype=dtype), np.diff(Asp.indptr)
    )
    return Asp.indices.astype(dtype, copy=False) - rows


def stencil_offsets(Asp: sps.csr_matrix, max_diags: int = 64,
                    return_diffs: bool = False):
    """Distinct diagonal offsets of A if there are few, else None.

    Short-circuits on a row sample first: unstructured matrices bail
    after O(sample) work instead of sorting all nnz diffs.
    ``return_diffs`` additionally returns the per-entry col-row diff
    array (entry order) so the caller's geo path reuses the single
    pass for ``axis_strengths`` — as ``(offs, diffs)``."""
    n = Asp.shape[0]
    if n > 4096:
        take = min(n, 512)
        stride = max(n // take, 1)
        rsel = np.arange(0, n, stride)
        sub = Asp[rsel]
        rows = np.repeat(rsel, np.diff(sub.indptr))
        if np.unique(
            sub.indices.astype(np.int64) - rows
        ).size > max_diags:
            return (None, None) if return_diffs else None
    # int32 diff arithmetic when both dimensions fit: the unique sort
    # runs ~2x faster and the offsets themselves are tiny either way
    use32 = max(Asp.shape) < np.iinfo(np.int32).max
    diffs = _col_diffs(Asp, np.int32 if use32 else np.int64)
    offs = np.unique(diffs)
    if offs.size > max_diags:
        return (None, None) if return_diffs else None
    offs = offs.astype(np.int64)
    return (offs, diffs) if return_diffs else offs


def infer_grid(offsets, n: int):
    """Infer (nx, ny, nz) with nx*ny*nz == n from stencil diagonal
    offsets; None if the offsets are not <=27-point-stencil shaped.

    A wrong-but-validating guess only degrades aggregate shapes (the
    Galerkin product is correct for any partition), never correctness.
    """
    offs = set(int(o) for o in offsets)
    pos = sorted(o for o in offs if o > 0)
    if not pos or n < 8:
        return None

    def allowed_set(nx, ny, nz):
        out = set()
        for a in (-1, 0, 1) if nx > 1 else (0,):
            for b in (-1, 0, 1) if ny > 1 else (0,):
                for c in (-1, 0, 1) if nz > 1 else (0,):
                    out.add(a + b * nx + c * nx * ny)
        return out

    cands_nx = {n}  # 1D chain
    for o in pos:
        for d in (o - 1, o, o + 1):
            if 2 <= d < n and n % d == 0:
                cands_nx.add(d)
    best = None
    best_score = None
    for nx in sorted(cands_nx):
        rem = n // nx
        cands_ny = {rem}
        for o in pos:
            for d in (o - 1, o, o + 1):
                if d >= 2 * nx and d % nx == 0 and rem % (d // nx) == 0:
                    cands_ny.add(d // nx)
        for ny in sorted(cands_ny):
            if ny < 1 or rem % ny:
                continue
            nz = rem // ny
            if offs <= allowed_set(nx, ny, nz):
                # prefer geometries whose primary strides are actual
                # offsets (true stencil axes), then the most cubic one
                score = (
                    (nx in offs or ny == 1)
                    + (nx * ny in offs or nz == 1),
                    -(max(nx, ny, nz) / max(min(nx, ny, nz), 1)),
                )
                if best is None or score > best_score:
                    best, best_score = (nx, ny, nz), score
    return best


def axis_strengths(Asp: sps.csr_matrix, nx: int, ny: int, nz: int,
                   diffs=None):
    """Mean |coupling| along each grid axis (offsets ±1, ±nx, ±nx·ny).

    Drives the semicoarsening decision: anisotropic stencils must be
    aggregated along the STRONG axis (classical strength-of-connection
    semantics), not by grid shape.
    """
    d = _col_diffs(Asp) if diffs is None else diffs
    av = np.abs(Asp.data)
    out = []
    for stride, dim in ((1, nx), (nx, ny), (nx * ny, nz)):
        if dim <= 1:
            out.append(0.0)
            continue
        m = np.abs(d) == stride
        out.append(float(av[m].mean()) if m.any() else 0.0)
    return out


def geo_block_shape(nx, ny, nz, passes, strengths=None):
    """Block shape (bx, by, bz) the geometric aggregation uses: each
    pass halves the axis with the largest remaining strength-to-block
    ratio (semicoarsening on anisotropic stencils)."""
    dims = [nx, ny, nz]
    block = [1, 1, 1]
    s = list(strengths) if strengths is not None else [1.0, 1.0, 1.0]
    smax = max(s) if max(s) > 0 else 1.0
    # breaking exact ties by dims keeps large axes first on cubes
    for _ in range(passes):
        ratios = [
            (s[a] / smax + 1e-9 * dims[a]) / block[a]
            if dims[a] > block[a]
            else 0.0
            for a in range(3)
        ]
        axis = int(np.argmax(ratios))
        if ratios[axis] <= 0.0:
            break
        block[axis] *= 2
    return tuple(block)


def geo_aggregate(
    nx: int, ny: int, nz: int, passes: int, strengths=None
) -> np.ndarray:
    """Blocked lexicographic aggregation on an (nx, ny, nz) grid.

    Each pass halves one axis: the one with the largest remaining
    coupling-strength-to-block ratio (``strengths`` from
    :func:`axis_strengths`; unit strengths when absent).  Isotropic
    stencils get the reference selector block shapes (SIZE_2 -> 2x1x1,
    SIZE_4 -> 2x2x1, SIZE_8 -> 2x2x2 on a cube); anisotropic stencils
    semicoarsen along the strong axis.  Coarse aggregates are numbered
    lexicographically on the coarse grid, so bandedness is preserved.
    """
    from amgx_tpu.ops.geo_transfer import blocked_map

    block = geo_block_shape(nx, ny, nz, passes, strengths)
    return blocked_map((nx, ny, nz), block)


def select_aggregates(Asp, cfg, scope):
    """The selector decision shared by the serial and distributed
    setup paths: geometric blocks when the matrix is stencil-structured
    (and structured_aggregation allows it, or selector is GEO),
    matching-based aggregation otherwise.

    Returns (agg, geo_info): geo_info is (grid, block) when the
    geometric path was taken (enables the dense-reduction Galerkin in
    geo_galerkin_dia), else None."""
    selector = str(cfg.get("selector", scope)).upper()
    passes = SELECTOR_PASSES.get(selector, 1)
    if passes is None:
        passes = int(cfg.get("aggregation_passes", scope))
    if selector == "DUMMY":
        # reference dummy.cu:51: aggregates[i] = i / aggregate_size
        size = max(int(cfg.get("aggregate_size", scope)), 1)
        agg = (np.arange(Asp.shape[0], dtype=np.int32) // size).astype(
            np.int32
        )
        return _maybe_print_agg_info(cfg, scope, selector, agg), None
    if bool(cfg.get("structured_aggregation", scope)) or selector == "GEO":
        # one diff pass serves the offset scan and the axis strengths
        offs, diffs = stencil_offsets(Asp, return_diffs=True)
        grid = (
            infer_grid(offs, Asp.shape[0]) if offs is not None else None
        )
        if grid is not None:
            strengths = axis_strengths(Asp, *grid, diffs=diffs)
            block = geo_block_shape(*grid, passes, strengths)
            agg = geo_aggregate(*grid, passes, strengths=strengths)
            return (
                _maybe_print_agg_info(cfg, scope, selector, agg),
                (grid, block),
            )
    # reference notay_weights=1 selects the Notay coupling formula
    # (computeEdgeWeights weight_formula branch)
    formula = (
        1 if bool(cfg.get("notay_weights", scope))
        else int(cfg.get("weight_formula", scope))
    )
    merge = bool(cfg.get("merge_singletons", scope))
    max_rounds = int(cfg.get("max_matching_iterations", scope))
    filter_alpha = (
        float(cfg.get("filter_weights_alpha", scope))
        if bool(cfg.get("filter_weights", scope)) else 0.0
    )
    serial = bool(cfg.get("serial_matching", scope))
    # max_unassigned_percentage early exit is honored only when the
    # config sets it: the registry default (0.05) is a reference-GPU
    # tuning; the deterministic handshake converges in few rounds and
    # an unconditional 5% early-out would change aggregates for every
    # existing config
    max_un = (
        float(cfg.get("max_unassigned_percentage", scope))
        if cfg.has("max_unassigned_percentage", scope) else 0.0
    )
    agg = aggregate(Asp, passes, formula, merge, max_rounds=max_rounds,
                    filter_alpha=filter_alpha, serial_matching=serial,
                    max_unassigned=max_un)
    return _maybe_print_agg_info(cfg, scope, selector, agg), None


def _maybe_print_agg_info(cfg, scope, selector, agg):
    """print_aggregation_info (reference aggregation selectors'
    printAggregationInfo): aggregate count + size histogram."""
    if bool(cfg.get("print_aggregation_info", scope)):
        from amgx_tpu.core.printing import emit

        nc = int(agg.max()) + 1 if agg.size else 0
        sizes = np.bincount(agg, minlength=max(nc, 1))
        emit(
            f"         Aggregation [{selector}]: {nc} aggregates over "
            f"{agg.shape[0]} rows; avg size "
            f"{agg.shape[0] / max(nc, 1):.2f}, max {int(sizes.max())}, "
            f"singletons {int((sizes == 1).sum())}"
        )
    return agg


# above this row count the dense-reduction Galerkin replaces the
# sparse product (memory: no A@P intermediate)
_GEO_RAP_MIN_ROWS = 4_000_000


def _decompose_offset(off, nx, ny, nz, reach=3):
    """Linear DIA offset -> (dx, dy, dz) stencil displacement with
    |d*| <= reach, or None when absent or AMBIGUOUS (thin grids make
    several displacements share a linear offset; guessing would build a
    wrong coarse operator, so the caller must fall back)."""
    found = []
    for dz in range(-reach, reach + 1):
        rem_z = off - dz * nx * ny
        for dy in range(-reach, reach + 1):
            dx = rem_z - dy * nx
            if -reach <= dx <= reach:
                found.append((dx, dy, dz))
    if len(found) != 1:
        return None
    return found[0]


def _geo_rap_keys(block, decs):
    """Static coarse-displacement keys of the geometric Galerkin
    reduction, in a deterministic order shared with the device
    program."""
    bx, by, bz = block
    keys = set()
    for dx, dy, dz in decs:
        for w in range(bz):
            for v in range(by):
                for u in range(bx):
                    keys.add(
                        ((u + dx) // bx, (v + dy) // by, (w + dz) // bz)
                    )
    return sorted(keys)


@functools.partial(
    jax.jit, static_argnames=("grid", "block", "decs")
)
def _geo_rap_device(dia, grid, block, decs):
    """Wrap check + windowed block reductions of the DIA diagonals as
    one XLA program (the on-device face of geo_galerkin_dia — the
    reference's csr_galerkin_product runs device-resident for the same
    reason, csr_multiply.cu:207).

    Returns (wrap_bad scalar, stacked coarse [n_keys, cz, cy, cx])
    with keys ordered by _geo_rap_keys."""
    nx, ny, nz = grid
    bx, by, bz = block
    cx, cy, cz = nx // bx, ny // by, nz // bz
    fz = jax.lax.broadcasted_iota(jnp.int32, (nz, ny, nx), 0)
    fy = jax.lax.broadcasted_iota(jnp.int32, (nz, ny, nx), 1)
    fx = jax.lax.broadcasted_iota(jnp.int32, (nz, ny, nx), 2)
    wrap_bad = jnp.bool_(False)
    keys = _geo_rap_keys(block, decs)
    accs = {
        k: jnp.zeros((cz, cy, cx), dtype=dia.dtype) for k in keys
    }
    for ki, (dx, dy, dz) in enumerate(decs):
        d3 = dia[ki].reshape(nz, ny, nx)
        valid = (
            (fx + dx >= 0) & (fx + dx < nx)
            & (fy + dy >= 0) & (fy + dy < ny)
            & (fz + dz >= 0) & (fz + dz < nz)
        )
        wrap_bad |= jnp.any(jnp.where(valid, 0.0, d3) != 0)
        V = dia[ki].reshape(cz, bz, cy, by, cx, bx)
        for w in range(bz):
            DZ = (w + dz) // bz
            for v in range(by):
                DY = (v + dy) // by
                for u in range(bx):
                    DX = (u + dx) // bx
                    accs[(DX, DY, DZ)] = (
                        accs[(DX, DY, DZ)] + V[:, w, :, v, :, u]
                    )
    return wrap_bad, jnp.stack([accs[k] for k in keys])


def _geo_rap_host(dia, grid, block, decs):
    """Exact host-precision twin of :func:`_geo_rap_device` — used
    when the device would downcast f64 (x64 disabled).  Same key
    order (_geo_rap_keys), same math."""
    nx, ny, nz = grid
    bx, by, bz = block
    cx, cy, cz = nx // bx, ny // by, nz // bz
    fz, fy, fx = np.meshgrid(
        np.arange(nz), np.arange(ny), np.arange(nx), indexing="ij"
    )
    keys = _geo_rap_keys(block, decs)
    accs = {k: np.zeros((cz, cy, cx), dtype=dia.dtype) for k in keys}
    for ki, (dx, dy, dz) in enumerate(decs):
        valid = (
            (fx + dx >= 0) & (fx + dx < nx)
            & (fy + dy >= 0) & (fy + dy < ny)
            & (fz + dz >= 0) & (fz + dz < nz)
        )
        if np.any(dia[ki].reshape(nz, ny, nx)[~valid] != 0):
            return True, None
        V = dia[ki].reshape(cz, bz, cy, by, cx, bx)
        for w in range(bz):
            DZ = (w + dz) // bz
            for v in range(by):
                DY = (v + dy) // by
                for u in range(bx):
                    DX = (u + dx) // bx
                    accs[(DX, DY, DZ)] += V[:, w, :, v, :, u]
    return False, np.stack([accs[k] for k in keys])


def geo_galerkin_dia(Asp, grid, block):
    """Galerkin product R A P for piecewise-constant GEO aggregation on
    a stencil matrix — computed as dense reshape-reductions over the
    DIA diagonals, no sparse-sparse products (the reference's SpGEMM
    hash kernels, csr_multiply_detail.cu, exist exactly because RAP is
    the setup bottleneck; for geometric blocks on a grid the product
    collapses to windowed diagonal sums).

    Returns the coarse operator as scipy CSR, or None when the
    decomposition does not apply (caller falls back to sparse RAP).

    Math: with P binary over (bx,by,bz) blocks, Ac[P,Q] =
    sum_{i in P, j in Q} A[i,j]; a fine entry on displacement
    (dx,dy,dz) at intra-block position (u,v,w) lands on the coarse
    displacement ((u+dx)//bx, (v+dy)//by, (w+dz)//bz).
    """
    nx, ny, nz = grid
    bx, by, bz = block
    if nx % bx or ny % by or nz % bz:
        return None  # ragged blocks: fall back
    with setup_phase("rap_diagonals"):
        found = _geo_rap_diagonals(Asp, grid, max(bx, by, bz))
    if found is None:
        return None
    dia, decs = found
    # wrap detection + windowed block reductions run as ONE jitted
    # device program (_geo_rap_device): on TPU the Galerkin reductions
    # — the largest remaining setup stage — leave the host.  When the
    # device would silently downcast f64 (x64 disabled, the usual TPU
    # setting), keep the exact host reductions instead: the coarse
    # operator's precision and the wrap check must not degrade.
    use_device = not (
        np.dtype(Asp.dtype) == np.float64
        and not jax.config.jax_enable_x64
    )
    if use_device:
        dia = setup_upload(dia, "rap_upload")
        with setup_phase("rap_device", "device"):
            wrap_bad, stacked = _geo_rap_device(
                dia, grid, (bx, by, bz), decs
            )
            wrap_bad = bool(wrap_bad)
        if not wrap_bad:
            stacked = setup_download(stacked, "rap_download")
    else:
        with setup_phase("rap_host"):
            wrap_bad, stacked = _geo_rap_host(
                dia, grid, (bx, by, bz), decs
            )
    if wrap_bad:
        # periodic/wrap diagonals (e.g. +-(nx-1)) carry nonzeros at
        # out-of-window rows — geometric attribution would be wrong
        return None
    with setup_phase("rap_assemble"):
        return _geo_rap_assemble(stacked, grid, (bx, by, bz), decs)


def _geo_rap_diagonals(Asp, grid, reach):
    """Dense diagonals ``[n_offsets, n]`` of a stencil matrix and each
    offset's (dx, dy, dz) displacement, or None when an offset does not
    decompose on ``grid``."""
    nx, ny, nz = grid
    n = nx * ny * nz
    rows_all = np.repeat(
        np.arange(n, dtype=np.int64), np.diff(Asp.indptr)
    )
    d_all = Asp.indices.astype(np.int64) - rows_all
    offs_arr = np.unique(d_all)
    dec = {}
    for off in offs_arr:
        d = _decompose_offset(int(off), nx, ny, nz, reach)
        if d is None:
            return None
        dec[int(off)] = d

    # all dense diagonals in ONE pass over the entries (CSR has no
    # duplicates, so plain fancy assignment suffices)
    k_all = np.searchsorted(offs_arr, d_all)
    dia = np.zeros((offs_arr.shape[0], n), dtype=Asp.dtype)
    dia[k_all, rows_all] = Asp.data
    return dia, tuple(dec[int(off)] for off in offs_arr)


def _geo_rap_assemble(stacked, grid, block, decs):
    """The coarse CSR from the stacked coarse diagonals of
    :func:`_geo_rap_device` / :func:`_geo_rap_host`."""
    nx, ny, nz = grid
    bx, by, bz = block
    cx, cy, cz = nx // bx, ny // by, nz // bz
    keys = _geo_rap_keys((bx, by, bz), decs)
    coarse = {k: stacked[i] for i, k in enumerate(keys)}

    nc = cx * cy * cz
    Z, Y, X = np.meshgrid(
        np.arange(cz), np.arange(cy), np.arange(cx), indexing="ij"
    )
    r_full = X + cx * (Y + cy * Z)
    rows_l, cols_l, vals_l = [], [], []
    for (DX, DY, DZ), acc in coarse.items():
        # valid coarse rows: the displaced coarse cell stays in-grid
        ok = (
            (X + DX >= 0) & (X + DX < cx)
            & (Y + DY >= 0) & (Y + DY < cy)
            & (Z + DZ >= 0) & (Z + DZ < cz)
        )
        c_off = DX + cx * (DY + cy * DZ)
        r = r_full[ok].ravel()
        rows_l.append(r)
        cols_l.append(r + c_off)
        vals_l.append(acc[ok].ravel())
    Ac = sps.csr_matrix(
        (
            np.concatenate(vals_l),
            (np.concatenate(rows_l), np.concatenate(cols_l)),
        ),
        shape=(nc, nc),
    )
    Ac.sum_duplicates()
    Ac.eliminate_zeros()
    Ac.sort_indices()
    return Ac


def build_aggregation_level(Asp, cfg, scope):
    """Returns (P, R, A_coarse, geo_info) for one aggregation level
    (reference aggregation_amg_level.cu:238-371 R/P from aggregate map +
    coarseAGenerator computeAOperator): scipy P, R and coarse operator,
    and the (grid, block) of a geometric aggregation (None otherwise),
    which lets the level's P and R take the structured-transfer format.
    Geometric aggregations compute the Galerkin product via dense
    diagonal reductions (geo_galerkin_dia) instead of sparse-sparse
    products."""
    # reference coarseAgenerator (coarse_A_generator.cu factory): both
    # registered generators (LOW_DEG hash SpGEMM, GALERKIN cusp product)
    # compute the same R A P; here one device/scipy Galerkin serves both
    # names, unknown names fail like the reference factory
    gen = str(cfg.get("coarseAgenerator", scope)).upper()
    if gen not in ("", "LOW_DEG", "GALERKIN", "THRUST", "DEFAULT"):
        raise KeyError(
            f"CoarseAGeneratorFactory '{gen}' has not been registered"
        )
    if not Asp.data.flags.writeable:
        # the serve path hands the READ-ONLY host_csr view of a padded
        # pattern, which can carry duplicate filler entries; scipy's
        # abs()/binops dedup IN PLACE, so canonicalize a private copy
        with setup_phase("canonicalize"):
            Asp = Asp.copy()
            Asp.sum_duplicates()
            Asp.sort_indices()
    with setup_phase("aggregation"):
        agg, geo_info = select_aggregates(Asp, cfg, scope)
    n = Asp.shape[0]
    nc = int(agg.max()) + 1
    with setup_phase("interp"):
        P = sps.csr_matrix(
            (np.ones(n, dtype=Asp.dtype), (np.arange(n), agg)),
            shape=(n, nc),
        )
        R = P.T.tocsr()
    with setup_phase("rap_execute"):
        Ac = None
        # the dense-reduction Galerkin avoids the A@P sparse
        # intermediate (which peaks at ~8x the fine operator's
        # memory); worth it above this size, below it scipy's product
        # is faster on host
        if geo_info is not None and n >= _GEO_RAP_MIN_ROWS:
            Ac = geo_galerkin_dia(Asp, *geo_info)
        if Ac is None:
            Ac = (R @ Asp @ P).tocsr()
            Ac.sum_duplicates()
            Ac.eliminate_zeros()  # structural parity with the geo path
            Ac.sort_indices()
    return P, R, Ac, geo_info
