"""Device-resident classical AMG setup: strength + PMIS + D1 + RAP.

Reference parity: the GPU-resident classical setup pipeline —
``src/classical/strength/ahat.cu``, ``src/classical/selectors/pmis.cu``
(657 LoC), ``src/classical/interpolators/distance1.cu``, and the
two-phase hash SpGEMM ``src/csr_multiply.cu:207`` /
``csr_multiply_detail.cu`` (2595 LoC) used for the Galerkin product.

TPU-first design (NOT a translation of the CUDA kernels):

  * Matrices live as row-sorted COO triples (``rows``/``cols``/``vals``)
    padded to power-of-two buckets with sentinel rows, so XLA programs
    are cached across levels/resetups whose sizes land in the same
    bucket.  CSR row pointers, when a product needs them, come from
    ``searchsorted`` over the sorted rows — on device.
  * Strength and interpolation are segment-reductions over the nnz axis
    (``segment_sum``/``segment_max``) — embarrassingly parallel, no
    scatter races to detect (SURVEY §5.2: determinism is structural).
  * PMIS is a ``lax.while_loop`` over edge-wise max-propagation, the
    same fixed point as the host selector (bit-identical C/F splits for
    a fixed seed: both sides compare the same f64 weights).
  * SpGEMM is ESC (expand - sort - compress): expand A-entry x B-row
    products via searchsorted offsets, ``lax.sort`` by (row, col) with
    two integer keys (no 64-bit combined key needed), then compress
    duplicates with a cumsum boundary scan + one scatter-add.  This is
    the "bound then compact" two-phase of the reference: the device
    computes exact sizes, the host reads back *scalars only* (the same
    O(levels) counter readbacks the reference does), then compaction
    runs into bucket-padded static shapes.

The pipeline covers the headline classical config (AHAT strength, PMIS,
D1 interpolation, Galerkin RAP).  Other selectors/interpolators fall
back to the host path (``amg/classical.py``) level-by-level.
"""

from __future__ import annotations

import functools

import numpy as np
import scipy.sparse as sps

import jax
import jax.numpy as jnp
from jax import lax

from amgx_tpu.amg.classical import _hash_weights as _hash_weights_raw
from amgx_tpu.core import profiling
from amgx_tpu.core.errors import ResourceError


def _hash_weights(n, seed=0):
    """Tie-break hash weights: O(n) numpy hashes that run between
    device kernels, as a host span inside the device span around
    them."""
    with profiling.setup_phase("hash_weights"):
        return _hash_weights_raw(n, seed=seed)


def _bucket(x: int, floor: int = 128) -> int:
    """Next power of two >= x (static-shape bucket)."""
    n = max(int(x), floor)
    return 1 << (n - 1).bit_length()


def _pad_coo(rows, cols, vals, size, n_rows):
    """Pad COO triples to ``size`` with sentinel rows (= n_rows) that
    sort after every valid entry and fall outside every segment."""
    nnz = rows.shape[0]
    pad = size - nnz
    assert pad >= 0
    r = np.concatenate([rows, np.full(pad, n_rows, rows.dtype)])
    c = np.concatenate([cols, np.zeros(pad, cols.dtype)])
    v = np.concatenate([vals, np.zeros(pad, vals.dtype)])
    return r, c, v


@jax.jit
def _sort_coo(rows, cols, vals):
    """(row, col)-ordered COO triples.  Every (row, col) sort of the
    pipeline is this one jitted program, so call sites and levels whose
    arrays share a padded size share one executable: the TPU compiler
    takes 17-29 s for this sort at 32K-32M elements, and a stable
    sort about three times as long (v5e, compiled without a chip).
    Unstable: the keys are unique, or the duplicates are summed right
    after (ESC compress), where their order moves only rounding."""
    return lax.sort((rows, cols, vals), num_keys=2, is_stable=False)


@functools.partial(jax.jit, static_argnames=("n_left",))
def _first_dev(rows, cols, n_left):
    """Duplicate-run heads of (row, col)-sorted COO, and their count."""
    first = jnp.concatenate([
        jnp.ones((1,), bool),
        (rows[1:] != rows[:-1]) | (cols[1:] != cols[:-1]),
    ]) & (rows < n_left)
    return first, first.sum()


# ----------------------------------------------------------------------
# strength of connection (AHAT)


@functools.partial(jax.jit, static_argnames=("n",))
def _strength_ahat_dev(rows, cols, vals, n, theta, max_row_sum):
    """Strong mask over A's nnz (reference strength/ahat.cu semantics,
    identical comparisons to the host ``strength_ahat``)."""
    valid = rows < n
    offd = valid & (rows != cols)
    neg = jnp.where(offd, -vals, 0.0)
    mneg = jax.ops.segment_max(
        neg, rows, num_segments=n + 1, indices_are_sorted=True
    )[:n]
    mabs = jax.ops.segment_max(
        jnp.where(offd, jnp.abs(vals), 0.0), rows,
        num_segments=n + 1, indices_are_sorted=True,
    )[:n]
    use_abs = mneg <= 0
    thresh = jnp.where(use_abs, mabs, mneg) * theta
    val = jnp.where(use_abs[jnp.minimum(rows, n - 1)], jnp.abs(vals), -vals)
    strong = offd & (val >= thresh[jnp.minimum(rows, n - 1)]) & (val > 0)
    # max_row_sum guard (weakened dependencies, reference core.cu)
    diag = jax.ops.segment_sum(
        jnp.where(valid & (rows == cols), vals, 0.0), rows,
        num_segments=n + 1, indices_are_sorted=True,
    )[:n]
    rs = jnp.abs(jax.ops.segment_sum(
        jnp.where(valid, vals, 0.0), rows,
        num_segments=n + 1, indices_are_sorted=True,
    )[:n])
    weak = rs > max_row_sum * jnp.abs(jnp.where(diag != 0, diag, 1.0))
    apply_guard = max_row_sum < 1.0 + 1e-12
    strong &= ~(apply_guard & weak[jnp.minimum(rows, n - 1)])
    return strong


# ----------------------------------------------------------------------
# PMIS C/F selection


@functools.partial(jax.jit, static_argnames=("n",))
def _pmis_dev(rows, cols, strong, n, w):
    """PMIS on the symmetrized strength graph (reference
    selectors/pmis.cu).  Bit-compatible with the host ``pmis_select``:
    the same f64 weights, the same undecided-neighbour max, the same
    C-neighbour fine sweep, 200-round cap."""
    rs = jnp.minimum(rows, n - 1)
    cs = jnp.minimum(cols, n - 1)
    edge = strong  # directed strong edges; used in both directions
    deg_out = jax.ops.segment_sum(
        edge.astype(jnp.int32), rows, num_segments=n + 1,
        indices_are_sorted=True,
    )[:n]
    deg_in = jax.ops.segment_sum(edge.astype(jnp.int32), cs,
                                 num_segments=n)
    iso = (deg_out + deg_in) == 0
    state0 = jnp.where(iso, jnp.int32(1), jnp.int32(0))

    def cond(carry):
        state, it = carry
        return (it < 200) & jnp.any(state == 0)

    def body(carry):
        state, it = carry
        und = state == 0
        wu = jnp.where(und, w, -1.0)
        act = edge & und[rs] & und[cs]
        # neighbour max over BOTH directions (symmetrized graph)
        m1 = jax.ops.segment_max(
            jnp.where(act, wu[cs], -1.0), rows,
            num_segments=n + 1, indices_are_sorted=True,
        )[:n]
        m2 = jax.ops.segment_max(
            jnp.where(act, wu[rs], -1.0), cs, num_segments=n
        )
        nbmax = jnp.maximum(m1, m2)
        state = jnp.where(und & (wu > nbmax), jnp.int32(1), state)
        # fine: undecided with a C neighbour (either direction)
        isC = (state == 1).astype(jnp.int32)
        c1 = jax.ops.segment_sum(
            jnp.where(edge, isC[cs], 0), rows,
            num_segments=n + 1, indices_are_sorted=True,
        )[:n]
        c2 = jax.ops.segment_sum(jnp.where(edge, isC[rs], 0), cs,
                                 num_segments=n)
        cnb = (c1 + c2) > 0
        state = jnp.where((state == 0) & cnb, jnp.int32(-1), state)
        return state, it + 1

    state, _ = lax.while_loop(cond, body, (state0, jnp.int32(0)))
    state = jnp.where(state == 0, jnp.int32(1), state)
    return (state == 1).astype(jnp.int8)


# ----------------------------------------------------------------------
# distance-1 direct interpolation


@functools.partial(jax.jit, static_argnames=("n",))
def _d1_weights_dev(rows, cols, vals, strong, cf, n):
    """Per-A-entry interpolation weights + keep mask (reference
    interpolators/distance1.cu; same sign-split alpha/beta formula as
    the host ``direct_interpolation``)."""
    valid = rows < n
    rs = jnp.minimum(rows, n - 1)
    cs = jnp.minimum(cols, n - 1)
    offd = valid & (rows != cols)
    isC_col = cf[cs] == 1

    def seg(x):
        return jax.ops.segment_sum(
            x, rows, num_segments=n + 1, indices_are_sorted=True
        )[:n]

    negm = vals < 0
    posm = offd & (vals > 0)
    sum_neg = seg(jnp.where(offd & negm, vals, 0.0))
    sum_pos = seg(jnp.where(posm, vals, 0.0))
    strongC = strong & isC_col
    sum_negC = seg(jnp.where(strongC & negm, vals, 0.0))
    sum_posC = seg(jnp.where(strongC & ~negm, vals, 0.0))
    diag = seg(jnp.where(valid & (rows == cols), vals, 0.0))
    diag = diag + jnp.where(sum_posC == 0, sum_pos, 0.0)
    alpha = jnp.where(sum_negC != 0, sum_neg / jnp.where(
        sum_negC != 0, sum_negC, 1.0), 0.0)
    beta = jnp.where(sum_posC != 0, sum_pos / jnp.where(
        sum_posC != 0, sum_posC, 1.0), 0.0)
    diag = jnp.where(diag != 0, diag, 1.0)
    keep = strongC & (cf[rs] == 0)
    coef = jnp.where(vals < 0, alpha[rs], beta[rs])
    pvals = -coef * vals / diag[rs]
    cmap = jnp.cumsum(cf.astype(jnp.int32)) - 1
    return pvals, keep, cmap


@functools.partial(jax.jit, static_argnames=("n", "out_size"))
def _assemble_p_dev(rows, cols, pvals, keep, cf, cmap, n, out_size,
                    nf, nc):
    """Compact F-row weights + C-row identity into P COO of static
    padded size ``out_size`` (phase 2 of bound-then-compact); the
    caller row-sorts it (:func:`_sort_coo`)."""
    # F entries -> slots [0, nf)
    posf = jnp.cumsum(keep.astype(jnp.int32)) - 1
    slotf = jnp.where(keep, posf, out_size)
    prow = jnp.full((out_size,), n, jnp.int32)
    pcol = jnp.zeros((out_size,), jnp.int32)
    pval = jnp.zeros((out_size,), pvals.dtype)
    prow = prow.at[slotf].set(rows, mode="drop")
    pcol = pcol.at[slotf].set(cmap[jnp.minimum(cols, n - 1)],
                              mode="drop")
    pval = pval.at[slotf].set(pvals, mode="drop")
    # C identity -> slots [nf, nf + nc)
    node = jnp.arange(n, dtype=jnp.int32)
    isC = cf == 1
    posc = jnp.cumsum(isC.astype(jnp.int32)) - 1
    slotc = jnp.where(isC, nf + posc, out_size)
    prow = prow.at[slotc].set(node, mode="drop")
    pcol = pcol.at[slotc].set(cmap, mode="drop")
    pval = pval.at[slotc].set(jnp.ones((n,), pvals.dtype), mode="drop")
    return prow, pcol, pval


def _transpose_dev(rows, cols, vals, n_rows_sentinel, n_cols):
    """COO transpose by (col, row) sort; sentinels move to col
    sentinel ``n_cols``."""
    tc = jnp.where(rows >= n_rows_sentinel, n_cols, cols)
    trow, tcol, tval = _sort_coo(tc, rows, vals)
    return _clear_sentinels(trow, tcol, tval, n_cols)


@jax.jit
def _clear_sentinels(trow, tcol, tval, n_cols):
    pad = trow >= n_cols
    return trow, jnp.where(pad, 0, tcol), jnp.where(pad, 0.0, tval)


# ----------------------------------------------------------------------
# sorted-pair lookup (binary search on (row, col) without 64-bit keys)


@jax.jit
def _lookup_sorted_pairs(qrows, qcols, rows, cols):
    """For each query (qrows[t], qcols[t]) find its index in the
    (row, col)-sorted COO arrays, or -1 when absent.  Lexicographic
    binary search — int32-safe (no combined 64-bit key)."""
    m = rows.shape[0]

    def lt(r1, c1, r2, c2):  # (r1,c1) < (r2,c2)
        return (r1 < r2) | ((r1 == r2) & (c1 < c2))

    def body(_, lohi):
        lo, hi = lohi
        mid = (lo + hi) // 2
        go_right = lt(rows[mid], cols[mid], qrows, qcols)
        return jnp.where(go_right, mid + 1, lo), jnp.where(
            go_right, hi, mid)

    lo = jnp.zeros(qrows.shape, jnp.int32)
    hi = jnp.full(qrows.shape, m, jnp.int32)
    steps = int(m).bit_length()
    lo, _ = lax.fori_loop(0, steps, body, (lo, hi))
    safe = jnp.minimum(lo, m - 1)
    hit = (rows[safe] == qrows) & (cols[safe] == qcols)
    return jnp.where(hit, safe, -1)


# ----------------------------------------------------------------------
# interpolation truncation (reference truncate.cu + interp_max_elements)


@functools.partial(jax.jit, static_argnames=("n", "apply_trunc",
                                             "max_el"))
def _truncate_weights_dev(prow, pcol, pval, n, trunc, apply_trunc,
                          max_el):
    """Per-entry keep mask + rescaled values for the row-sorted P COO
    (same semantics as the host ``truncate_interp``: drop below
    trunc*max|row|, keep the max_el largest per row with the host's
    stable original-position tie-break, rescale to preserve row sums)."""
    valid = prow < n
    rs = jnp.minimum(prow, n - 1)
    absd = jnp.where(valid, jnp.abs(pval), 0.0)
    keep = valid
    if apply_trunc:
        rmax = jax.ops.segment_max(
            absd, prow, num_segments=n + 1, indices_are_sorted=True
        )[:n]
        keep &= absd >= trunc * rmax[rs]
    if max_el >= 0:
        # rank within row by descending |val|, original position as the
        # stable tie-break (host np.lexsort((arange, -absd, rows)))
        pos = jnp.arange(prow.shape[0], dtype=jnp.int32)
        srow, _, spos = lax.sort((prow, -absd, pos), num_keys=3)
        indptr = jnp.searchsorted(
            srow, jnp.arange(n + 1, dtype=srow.dtype), side="left")
        rank_sorted = jnp.arange(prow.shape[0], dtype=jnp.int32) - \
            indptr[jnp.minimum(srow, n - 1)].astype(jnp.int32)
        rank = jnp.zeros(prow.shape[0], jnp.int32).at[spos].set(
            rank_sorted)
        keep &= rank < max_el
    rs_old = jax.ops.segment_sum(
        jnp.where(valid, pval, 0.0), prow,
        num_segments=n + 1, indices_are_sorted=True)[:n]
    rs_new = jax.ops.segment_sum(
        jnp.where(keep, pval, 0.0), prow,
        num_segments=n + 1, indices_are_sorted=True)[:n]
    scale = jnp.where(rs_new != 0,
                      rs_old / jnp.where(rs_new != 0, rs_new, 1.0), 1.0)
    newval = pval * keep * scale[rs]
    keep &= newval != 0  # eliminate_zeros parity
    return keep, newval


def truncate_interp_device(prow, pcol, pval, nnzP, n, trunc, max_el):
    """Device truncation; returns compacted row-sorted COO + nnz."""
    apply_trunc = trunc < 1.0
    if (not apply_trunc and max_el < 0) or nnzP == 0:
        return prow, pcol, pval, nnzP
    keep, newval = _truncate_weights_dev(
        prow, pcol, pval, n, trunc, apply_trunc, int(max_el))
    return _compact_masked(prow, pcol, newval, keep, n)


# ----------------------------------------------------------------------
# ESC SpGEMM


@functools.partial(jax.jit, static_argnames=("n_left",))
def _spgemm_bound_dev(a_rows, a_cols, b_indptr, n_left):
    """Phase 1 (bound): expansion length = sum over valid A entries of
    the B row length at the entry's column."""
    valid = a_rows < n_left
    ac = jnp.minimum(a_cols, b_indptr.shape[0] - 2)
    cnt = jnp.where(valid, b_indptr[ac + 1] - b_indptr[ac], 0)
    return jnp.cumsum(cnt.astype(jnp.int64)), cnt


@functools.partial(jax.jit, static_argnames=("E", "n_left"))
def _spgemm_expand_dev(a_rows, a_cols, a_vals, cum, cnt,
                       b_indptr, b_cols, b_vals, E, n_left):
    """Phase 2 (expand): materialize all partial products; the caller
    sorts them by output (row, col)."""
    t = jnp.arange(E, dtype=cum.dtype)
    e = jnp.searchsorted(cum, t, side="right")
    live = e < a_rows.shape[0]
    e = jnp.minimum(e, a_rows.shape[0] - 1)
    start = cum[e] - cnt[e]
    off = t - start
    ac = jnp.minimum(a_cols[e], b_indptr.shape[0] - 2)
    bflat = jnp.minimum(
        b_indptr[ac] + off.astype(b_indptr.dtype),
        b_cols.shape[0] - 1,
    )
    live &= a_rows[e] < n_left
    rows = jnp.where(live, a_rows[e], n_left).astype(jnp.int32)
    cols = jnp.where(live, b_cols[bflat], 0).astype(jnp.int32)
    vals = jnp.where(live, a_vals[e] * b_vals[bflat], 0.0)
    return rows, cols, vals


def _spgemm_compress_impl(rows, cols, vals, first, out_size, n_left):
    """Phase 3 (compress): scatter-add duplicate runs into the padded
    output buffer (static ``out_size``)."""
    valid = rows < n_left
    seg = jnp.cumsum(first.astype(jnp.int32)) - 1
    slot = jnp.where(valid, seg, out_size)
    orow = jnp.full((out_size,), n_left, jnp.int32)
    ocol = jnp.zeros((out_size,), jnp.int32)
    oval = jnp.zeros((out_size,), vals.dtype)
    orow = orow.at[jnp.where(first, slot, out_size)].set(
        rows, mode="drop")
    ocol = ocol.at[jnp.where(first, slot, out_size)].set(
        cols, mode="drop")
    oval = oval.at[slot].add(vals, mode="drop")
    return orow, ocol, oval


@functools.lru_cache(maxsize=2)
def _compress_jit(donate: bool):
    """Compress executable, with the expand/sort intermediates DONATED
    on accelerator backends: the sorted triples + boundary mask are
    dead after compression, and donating them lets XLA reuse those
    HBM buffers for the (bucket-padded, same-scale) outputs instead of
    holding both live — the peak-memory term of the ESC Galerkin
    chain.  CPU skips donation (unimplemented there; XLA warns)."""
    if donate:
        return jax.jit(
            _spgemm_compress_impl,
            static_argnames=("out_size",),
            donate_argnums=(0, 1, 2, 3),
        )
    return jax.jit(_spgemm_compress_impl, static_argnames=("out_size",))


def _spgemm_compress_dev(rows, cols, vals, first, out_size, n_left):
    from amgx_tpu.solvers.base import donation_enabled

    return _compress_jit(donation_enabled())(
        rows, cols, vals, first, out_size, n_left
    )


def _indptr_from_sorted_rows(rows, n):
    return jnp.searchsorted(rows, jnp.arange(n + 1, dtype=rows.dtype),
                            side="left")


class DeviceSetupOverflow(ResourceError):
    """An ESC SpGEMM expansion exceeds int32 addressing; the caller
    builds this level on the host (scipy) instead.  The one device-setup
    failure amg/hierarchy.py sends to the host — every other one raises.
    A :class:`~amgx_tpu.core.errors.ResourceError`."""


# ESC expansion entries are addressed with (at most) int32 arithmetic
# on device: when jax_enable_x64 is off, jnp.int64 silently degrades to
# int32, so any larger expansion would wrap and corrupt the Galerkin
# product.  Detected on HOST (numpy int64, immune to the degradation).
_SPGEMM_MAX_EXPANSION = 2**31 - 1


def spgemm_device(a_rows, a_cols, a_vals, n_left,
                  b_rows, b_cols, b_vals, n_mid):
    """C = A @ B on device (ESC).  A, B are row-sorted padded COO; the
    host round-trips are the expansion bound and the output nnz
    (reference two-phase csr_multiply.cu:207 counter readbacks).
    Returns (rows, cols, vals, nnz) with padded static shapes.

    Raises :class:`DeviceSetupOverflow` when the expansion would
    exceed int32 range (ADVICE r5 medium): the device cumsum computes
    in int32 whenever ``jax_enable_x64`` is off, so the bound is
    re-derived in host numpy int64 — per-entry counts each fit int32,
    only their SUM can wrap — and oversized products are rejected
    before any wrapped index can silently corrupt the product.
    """
    b_indptr = _indptr_from_sorted_rows(b_rows, n_mid)
    cum, cnt = _spgemm_bound_dev(a_rows, a_cols, b_indptr, n_left)
    # host int64 bound (sync #1 — an array pull, the overflow guard's
    # price; the device `cum` stays int32-safe once total is in range)
    total = int(np.asarray(cnt, dtype=np.int64).sum())
    if total > _SPGEMM_MAX_EXPANSION:
        raise DeviceSetupOverflow(
            f"ESC SpGEMM expansion {total} exceeds int32 range; "
            "use the host builder for this level"
        )
    E = _bucket(total)
    rows, cols, vals, first, nnz_dev = _sort_first_dev(
        *_spgemm_expand_dev(a_rows, a_cols, a_vals, cum, cnt, b_indptr,
                            b_cols, b_vals, E, n_left),
        n_left,
    )
    nnz = int(nnz_dev)  # scalar sync #2
    out_size = _bucket(nnz)
    orow, ocol, oval = _spgemm_compress_dev(
        rows, cols, vals, first, out_size, n_left
    )
    return orow, ocol, oval, nnz


# ----------------------------------------------------------------------
# COO utilities shared by the aggressive / D2 paths


def _sort_first_dev(rows, cols, vals, n_left):
    rows, cols, vals = _sort_coo(rows, cols, vals)
    return (rows, cols, vals, *_first_dev(rows, cols, n_left))


def coalesce_coo_device(rows, cols, vals, n_left):
    """Sort by (row, col) and sum duplicates; returns padded sorted COO
    + exact nnz (one scalar sync)."""
    rows, cols, vals, first, nnz_dev = _sort_first_dev(
        rows, cols, vals, n_left)
    nnz = int(nnz_dev)
    out = _bucket(nnz)
    return (*_spgemm_compress_dev(rows, cols, vals, first, out, n_left),
            nnz)


@functools.partial(jax.jit, static_argnames=("out_size",))
def _compact_coo_dev(rows, cols, vals, keep, out_size, sentinel_row):
    """Compact masked COO entries into a padded buffer, preserving
    order (entries must already be (row, col)-sorted)."""
    pos = jnp.cumsum(keep.astype(jnp.int32)) - 1
    slot = jnp.where(keep, pos, out_size)
    orow = jnp.full((out_size,), sentinel_row, jnp.int32).at[slot].set(
        rows, mode="drop")
    ocol = jnp.zeros((out_size,), jnp.int32).at[slot].set(
        cols, mode="drop")
    oval = jnp.zeros((out_size,), vals.dtype).at[slot].set(
        vals, mode="drop")
    return orow, ocol, oval


def _compact_masked(rows, cols, vals, keep, sentinel_row):
    nnz = int(keep.sum())  # scalar sync
    out = _bucket(nnz)
    r, c, v = _compact_coo_dev(rows, cols, vals, keep, out,
                               sentinel_row)
    return r, c, v, nnz


# ----------------------------------------------------------------------
# aggressive two-stage PMIS (reference selectors AGGRESSIVE_PMIS)


def aggressive_pmis_device(rows, cols, vals, strong, n, dtype):
    """Two-stage aggressive coarsening: PMIS on S, then PMIS (seed 1)
    among the stage-1 C points on the distance-2 graph S + S@S —
    bit-compatible with the host ``aggressive_pmis_select``."""
    fdt = jnp.float64 if dtype == np.float64 else jnp.float32
    lam = jax.ops.segment_sum(
        strong.astype(fdt), jnp.minimum(cols, n - 1), num_segments=n)
    w0 = lam + jnp.asarray(_hash_weights(n, seed=0), fdt)
    cf1 = _pmis_dev(rows, cols, strong, n, w0).astype(jnp.int32)
    nc1 = int(cf1.sum())  # scalar sync
    if nc1 <= 1:
        return cf1.astype(jnp.int8), nc1
    # S as explicit COO (binary values)
    ones = jnp.ones(rows.shape, fdt)
    srow, scol, sval, nnzS = _compact_masked(rows, cols, ones, strong, n)
    # S2 = S U S@S
    ss = spgemm_device(srow, scol, sval, n, srow, scol, sval, n)
    r2 = jnp.concatenate([srow, ss[0]])
    c2 = jnp.concatenate([scol, ss[1]])
    v2 = jnp.concatenate([sval, jnp.ones(ss[0].shape, fdt)])
    r2, c2, v2, nnz2 = coalesce_coo_device(r2, c2, v2, n)
    # restrict to C x C, drop diagonal, renumber by cmap1
    cmap1 = jnp.cumsum(cf1) - 1
    rs2 = jnp.minimum(r2, n - 1)
    cs2 = jnp.minimum(c2, n - 1)
    keepC = (r2 < n) & (cf1[rs2] == 1) & (cf1[cs2] == 1) & (r2 != c2)
    rc = jnp.where(keepC, cmap1[rs2], nc1).astype(jnp.int32)
    cc = jnp.where(keepC, cmap1[cs2], 0).astype(jnp.int32)
    crow, ccol, cvalv, nnzC = _compact_masked(rc, cc, v2, keepC, nc1)
    edgeC = crow < nc1
    lam2 = jax.ops.segment_sum(
        edgeC.astype(fdt), jnp.minimum(ccol, nc1 - 1), num_segments=nc1)
    w2 = lam2 + jnp.asarray(_hash_weights(nc1, seed=1), fdt)
    cf2 = _pmis_dev(crow, ccol, edgeC, nc1, w2)
    # scatter back: final C = stage-1 C that survived stage 2
    cf = (cf1 == 1) & (cf2.astype(jnp.int32)[
        jnp.minimum(cmap1, nc1 - 1)] == 1)
    return cf.astype(jnp.int8), int(cf.sum())


# ----------------------------------------------------------------------
# multipass interpolation (reference interpolators/multipass.cu)


def multipass_interpolation_device(rows, cols, vals, strong, cf, n,
                                   max_passes=10):
    """Pass-k F rows interpolate through strong assigned neighbours'
    P rows (same recurrence as the host ``multipass_interpolation``;
    each pass is one ESC SpGEMM of the scaled strong-assigned slice
    with the current P)."""
    valid = rows < n
    rs = jnp.minimum(rows, n - 1)
    cs = jnp.minimum(cols, n - 1)

    def seg(x):
        return jax.ops.segment_sum(
            x, rows, num_segments=n + 1, indices_are_sorted=True)[:n]

    diag = seg(jnp.where(valid & (rows == cols), vals, 0.0))
    row_total = seg(jnp.where(valid, vals, 0.0)) - diag
    strongm = strong & (rows != cols)
    cf_b = cf.astype(jnp.int32)
    cmap = jnp.cumsum(cf_b) - 1
    nc = int(cf_b.sum())
    assigned = cf_b == 1

    # P starts as the C-point identity block
    node = jnp.arange(n, dtype=jnp.int32)
    isC = cf_b == 1
    p_size = _bucket(nc)
    posc = jnp.cumsum(isC) - 1
    slotc = jnp.where(isC, posc, p_size)
    prow = jnp.full((p_size,), n, jnp.int32).at[slotc].set(
        node, mode="drop")
    pcol = jnp.zeros((p_size,), jnp.int32).at[slotc].set(
        cmap, mode="drop")
    pval = jnp.zeros((p_size,), vals.dtype).at[slotc].set(
        jnp.ones((n,), vals.dtype), mode="drop")
    nnzP = nc

    for _ in range(max_passes):
        # ready: unassigned rows with a strong assigned neighbour
        pat = seg(jnp.where(strongm & assigned[cs], 1.0, 0.0)) > 0
        ready = (~assigned) & pat
        n_ready = int(ready.sum())  # scalar sync
        if n_ready == 0:
            break
        picked = strongm & ready[rs] & assigned[cs]
        strong_sum = seg(jnp.where(picked, vals, 0.0))
        atil = diag + (row_total - strong_sum)
        atil = jnp.where(atil != 0, atil, 1.0)
        wvals = jnp.where(picked, -vals / atil[rs], 0.0)
        wr, wc, wv, nnzW = _compact_masked(rows, cols, wvals, picked, n)
        wp = spgemm_device(wr, wc, wv, n, prow, pcol, pval, n)
        # new rows are disjoint from existing P rows: concat + sort
        r3 = jnp.concatenate([prow, wp[0]])
        c3 = jnp.concatenate([pcol, wp[1]])
        v3 = jnp.concatenate([pval, wp[2]])
        prow, pcol, pval, nnzP = coalesce_coo_device(r3, c3, v3, n)
        assigned = assigned | ready
    return prow, pcol, pval, nnzP, nc


# ----------------------------------------------------------------------
# distance-2 "standard" interpolation (reference interpolators/
# distance2.cu, 2274 LoC; hypre BoomerAMG standard formulation)


def standard_interpolation_device(rows, cols, vals, strong, cf, n,
                                  dtype):
    """D2 interpolation on device.  Same algebra as the host
    ``standard_interpolation``, expressed over n-space COO slices with
    ESC products; the pair-dependent denominators d_ik are entries of
    (T @ A_FC_neg^T) sampled on the strong-F-F pattern by lexicographic
    binary search (no 64-bit keys)."""
    valid = rows < n
    rs = jnp.minimum(rows, n - 1)
    cs = jnp.minimum(cols, n - 1)
    cf_b = cf.astype(jnp.int32)
    cmap = jnp.cumsum(cf_b) - 1
    nc = int(cf_b.sum())  # scalar sync
    isF_r = cf_b[rs] == 0
    isC_c = cf_b[cs] == 1
    isF_c = cf_b[cs] == 0
    offd = valid & (rows != cols)

    def seg(x):
        return jax.ops.segment_sum(
            x, rows, num_segments=n + 1, indices_are_sorted=True)[:n]

    diag = seg(jnp.where(valid & (rows == cols), vals, 0.0))

    m_sfc = valid & strong & isF_r & isC_c
    m_sff = offd & strong & isF_r & isF_c
    m_afc = valid & isF_r & isC_c
    # sign restriction: redistribution uses entries opposite in sign to
    # the row diagonal (host keep_neg)
    m_neg = m_afc & (vals * diag[rs] < 0)

    fr, fc_, fv, nnz_fc = _compact_masked(rows, cols, vals, m_sfc, n)
    gr, gc, gv, nnz_ff = _compact_masked(rows, cols, vals, m_sff, n)
    hr, hc, hv, nnz_neg = _compact_masked(rows, cols, vals, m_neg, n)

    one = jnp.ones
    # T = SFCb U SFFb @ SFCb   (binary patterns)
    sfc1 = one(fr.shape, fv.dtype) * (fr < n)
    sff1 = one(gr.shape, gv.dtype) * (gr < n)
    tprod = spgemm_device(gr, gc, sff1, n, fr, fc_, sfc1, n)
    tr = jnp.concatenate([fr, tprod[0]])
    tc = jnp.concatenate([fc_, tprod[1]])
    tv = jnp.concatenate([sfc1, one(tprod[0].shape, fv.dtype)])
    tr, tc, tv, nnzT = coalesce_coo_device(tr, tc, tv, n)
    tbin = jnp.where(tr < n, one(tr.shape, fv.dtype), 0.0)

    # E = T @ A_FC_neg^T ; d_ik sampled at SFF entries
    ntr, ntc, ntv = _transpose_dev(hr, hc, hv, n, n)
    E = spgemm_device(tr, tc, tbin, n, ntr, ntc, ntv, n)
    d_idx = _lookup_sorted_pairs(gr, gc, E[0], E[1])
    d_vals = jnp.where(d_idx >= 0, E[2][jnp.maximum(d_idx, 0)], 0.0)
    d_vals = jnp.where(gr < n, d_vals, 0.0)

    b_vals = jnp.where(d_vals != 0,
                       gv / jnp.where(d_vals != 0, d_vals, 1.0), 0.0)
    # B @ A_FC_neg
    ba = spgemm_device(gr, gc, b_vals, n, hr, hc, hv, n)
    # Wnum = (AsFC + B @ A_FC_neg) masked to T
    wr = jnp.concatenate([fr, ba[0]])
    wc = jnp.concatenate([fc_, ba[1]])
    wv = jnp.concatenate([fv, ba[2]])
    wr, wc, wv, nnzW = coalesce_coo_device(wr, wc, wv, n)
    t_idx = _lookup_sorted_pairs(wr, wc, tr, tc)
    inT = (t_idx >= 0) & (wr < n)

    # modified diagonal
    row_total = seg(jnp.where(valid, vals, 0.0)) - diag
    strong_sum = seg(jnp.where(m_sfc | m_sff, vals, 0.0))
    weak_sum = row_total - strong_sum
    undis = jax.ops.segment_sum(
        jnp.where((d_vals == 0) & (gr < n), gv, 0.0),
        jnp.minimum(gr, n - 1), num_segments=n)
    atil = diag + weak_sum + undis
    atil = jnp.where(atil != 0, atil, 1.0)
    wv = jnp.where(inT, -wv / atil[jnp.minimum(wr, n - 1)], 0.0)

    # assemble P: F rows from Wnum(T), C identity
    nnzWk = int(inT.sum())  # scalar sync
    p_size = _bucket(nnzWk + nc)
    posw = jnp.cumsum(inT.astype(jnp.int32)) - 1
    slotw = jnp.where(inT, posw, p_size)
    prow = jnp.full((p_size,), n, jnp.int32).at[slotw].set(
        wr, mode="drop")
    pcol = jnp.zeros((p_size,), jnp.int32).at[slotw].set(
        cmap[jnp.minimum(wc, n - 1)], mode="drop")
    pval = jnp.zeros((p_size,), wv.dtype).at[slotw].set(
        wv, mode="drop")
    node = jnp.arange(n, dtype=jnp.int32)
    isC = cf_b == 1
    posc = jnp.cumsum(isC) - 1
    slotc = jnp.where(isC, nnzWk + posc, p_size)
    prow = prow.at[slotc].set(node, mode="drop")
    pcol = pcol.at[slotc].set(cmap, mode="drop")
    pval = pval.at[slotc].set(jnp.ones((n,), wv.dtype), mode="drop")
    prow, pcol, pval = _sort_coo(prow, pcol, pval)
    return prow, pcol, pval, nnzWk + nc, nc


# ----------------------------------------------------------------------
# Galerkin chain


def galerkin_rap_device(rows, cols, vals, prow, pcol, pval, n, nc):
    """The per-level Galerkin tail — R = P^T, AP = A @ P, Ac = R @ AP
    — as ONE driver call over the ESC kernels, with the expand/sort
    intermediates donated into their compress stages (_compress_jit).
    The only host round-trips are the four scalar size syncs of the
    two products (the reference csr_multiply.cu counter readbacks),
    counted through the setup-sync hook.  Returns ((rrow, rcol, rval),
    (ac_rows, ac_cols, ac_vals, nnz_ac))."""
    rrow, rcol, rval = _transpose_dev(prow, pcol, pval, n, nc)
    ap = spgemm_device(rows, cols, vals, n, prow, pcol, pval, n)
    ac = spgemm_device(rrow, rcol, rval, nc, ap[0], ap[1], ap[2], n)
    profiling.count_setup_sync(4)
    return (rrow, rcol, rval), ac


# ----------------------------------------------------------------------
# orchestration


def device_setup_eligible(cfg, scope, level_id: int,
                          dtype=None) -> bool:
    """The device pipeline covers the headline classical path; anything
    else falls back to the host builder per level.  f64 problems need
    jax_enable_x64 or the arrays would silently downcast (same guard as
    aggregation.geo_galerkin_dia)."""
    if dtype is not None and np.dtype(dtype) == np.float64 \
            and not jax.config.jax_enable_x64:
        return False
    strength = str(cfg.get("strength", scope)).upper()
    selector = str(cfg.get("selector", scope)).upper()
    interp = str(cfg.get("interpolator", scope)).upper()
    aggressive_levels = int(cfg.get("aggressive_levels", scope))
    aggressive = (
        level_id < aggressive_levels
        or selector in ("AGGRESSIVE_PMIS", "AGGRESSIVE_HMIS")
    )
    if aggressive:
        # aggressive stage: two-stage PMIS + MULTIPASS on device
        # (AGGRESSIVE_HMIS uses the PMIS-based stage like the host)
        return strength == "AHAT"
    return (
        strength == "AHAT"
        and selector == "PMIS"
        and interp in ("D1", "D2", "STD", "STANDARD", "MULTIPASS")
    )


def _coo_to_scipy(rows, cols, vals, nnz, shape):
    """Row-major-sorted unique COO -> scipy CSR without a host sort
    (indptr by bincount; O(nnz) array assembly only)."""
    r, c, v = profiling.setup_download(
        (rows[:nnz], cols[:nnz], vals[:nnz]), "device_download")
    indptr = np.zeros(shape[0] + 1, np.int64)
    np.cumsum(np.bincount(r, minlength=shape[0]), out=indptr[1:])
    return sps.csr_matrix((v, c.astype(np.int64), indptr), shape=shape)


def build_classical_level_device(Asp, cfg, scope, level_id: int = 0):
    """One classical level on device (strength -> PMIS -> D1 -> RAP).

    Returns (P, R, Ac) as scipy CSR for the driver loop.  The build is
    five set-up spans: ``device_pad`` (host), ``device_upload``
    (transfer), ``device_build`` (device, from the first dispatch to
    the Galerkin product's readiness; its host hashes are a nested
    ``hash_weights`` host span), ``device_download`` (transfer) and
    ``device_assemble`` (host); the scalar syncs are counted through
    ``profiling.count_setup_sync``.  Raises nothing: callers gate on
    :func:`device_setup_eligible`.
    """
    theta = float(cfg.get("strength_threshold", scope))
    max_row_sum = float(cfg.get("max_row_sum", scope))
    selector = str(cfg.get("selector", scope)).upper()
    interp = str(cfg.get("interpolator", scope)).upper()
    trunc = float(cfg.get("interp_truncation_factor", scope))
    max_el = int(cfg.get("interp_max_elements", scope))
    aggressive_levels = int(cfg.get("aggressive_levels", scope))
    aggressive_interp = str(
        cfg.get("aggressive_interpolator", scope)).upper()
    aggressive = (
        level_id < aggressive_levels
        or selector in ("AGGRESSIVE_PMIS", "AGGRESSIVE_HMIS")
    )

    with profiling.setup_phase("device_pad"):
        A = Asp.tocsr()
        n = A.shape[0]
        nnz = A.indices.shape[0]
        rows_np = np.repeat(np.arange(n, dtype=np.int32),
                            np.diff(A.indptr))
        size = _bucket(nnz)
        r_np, c_np, v_np = _pad_coo(
            rows_np, A.indices.astype(np.int32), A.data, size, n
        )
    rows, cols, vals = profiling.setup_upload(
        (r_np, c_np, v_np), "device_upload")
    with profiling.setup_phase("device_build", "device"):
        (prow, pcol, pval, nnzP, nc), (rrow, rcol, rval), ac = \
            _build_level_dev(rows, cols, vals, n, Asp.dtype, theta,
                             max_row_sum, selector, interp, trunc,
                             max_el, aggressive, aggressive_interp)
    with profiling.setup_phase("device_assemble"):
        P = _coo_to_scipy(prow, pcol, pval, nnzP, (n, nc))
        R = _coo_to_scipy(rrow, rcol, rval, nnzP, (nc, n))
        Ac = _coo_to_scipy(ac[0], ac[1], ac[2], ac[3], (nc, nc))
    return P, R, Ac


def _build_level_dev(rows, cols, vals, n, dtype, theta, max_row_sum,
                     selector, interp, trunc, max_el, aggressive,
                     aggressive_interp):
    """The device programs of one classical level, from strength to
    the Galerkin product, returned once the product is ready:
    ``(P coo, nnz P, coarse rows), R coo, Ac coo and nnz``."""
    import warnings

    fdt = jnp.float64 if vals.dtype == jnp.float64 else jnp.float32
    strong = _strength_ahat_dev(rows, cols, vals, n, theta, max_row_sum)

    if aggressive:
        if aggressive_interp != "MULTIPASS":
            warnings.warn(
                f"aggressive interpolator {aggressive_interp}: "
                "using MULTIPASS"
            )
        cf, nc = aggressive_pmis_device(rows, cols, vals, strong, n,
                                        dtype)
        profiling.count_setup_sync(4)
        prow, pcol, pval, nnzP, nc = multipass_interpolation_device(
            rows, cols, vals, strong, cf, n)
        profiling.count_setup_sync(4)
    else:
        # PMIS weights: S^T degree + hash (f64, identical to host;
        # seed=0 matches the host pmis_select stage-0 seed)
        lam = jax.ops.segment_sum(
            strong.astype(fdt), jnp.minimum(cols, n - 1),
            num_segments=n,
        )
        wdev = lam + jnp.asarray(
            _hash_weights(n, seed=0), fdt
        )
        cf = _pmis_dev(rows, cols, strong, n, wdev)
        if interp == "MULTIPASS":
            prow, pcol, pval, nnzP, nc = multipass_interpolation_device(
                rows, cols, vals, strong, cf, n)
            profiling.count_setup_sync(4)
        elif interp == "D1":
            pvals, keep, cmap = _d1_weights_dev(
                rows, cols, vals, strong, cf.astype(jnp.int32), n)
            nf = int(keep.sum())     # scalar sync
            nc = int(cf.sum())       # scalar sync
            profiling.count_setup_sync(2)
            nnzP = nf + nc
            p_size = _bucket(nnzP)
            prow, pcol, pval = _sort_coo(*_assemble_p_dev(
                rows, cols, pvals, keep, cf.astype(jnp.int32), cmap,
                n, p_size, jnp.int32(nf), jnp.int32(nc),
            ))
        else:  # D2 / STD / STANDARD
            prow, pcol, pval, nnzP, nc = standard_interpolation_device(
                rows, cols, vals, strong, cf, n, dtype)
            profiling.count_setup_sync(6)

    prow, pcol, pval, nnzP = truncate_interp_device(
        prow, pcol, pval, nnzP, n, trunc, max_el)
    # Galerkin tail (transpose + AP + RAP) as one driver call with
    # donated expand/sort intermediates
    (rrow, rcol, rval), ac = galerkin_rap_device(
        rows, cols, vals, prow, pcol, pval, n, nc
    )
    jax.block_until_ready(ac[2])
    return (prow, pcol, pval, nnzP, nc), (rrow, rcol, rval), ac
