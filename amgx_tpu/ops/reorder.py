"""Bandwidth-reducing unknown renumbering (RCM).

TPU rationale: the windowed Pallas SpMV kernel (ops.pallas_well) costs
each 1024-row tile a walk over the 128-lane x rows its columns touch.
Stencil matrices touch few by construction; unstructured matrices get
there from a reverse-Cuthill-McKee reordering, which is how this
framework answers
the reference's cuSPARSE-on-arbitrary-CSR performance
(/root/reference/src/amgx_cusparse.cu) on gather-hostile hardware.

Two consumers:
  * Solver.setup (solvers/base.py): permutes the whole system once at
    the solve boundary (mirrors the Scaler hook, reference
    solver.cu:667-676); vectors are permuted on entry / inverse-
    permuted on exit, so callers never see the internal ordering.
  * AMG setup (amg/hierarchy.py): renumbers each coarse level's
    unknowns — coarse numbering is an internal degree of freedom, so
    the permutation is folded into P/R and never observable.
"""

from __future__ import annotations

import numpy as np

from amgx_tpu.core import matrix as _m

# mean x rows a tile's kernel walk touches, below which reordering has
# nothing left to win
_GOOD_ROWS = 16


def rcm_permutation(sp) -> np.ndarray:
    """Reverse-Cuthill-McKee ordering of a scipy CSR matrix."""
    from scipy.sparse.csgraph import reverse_cuthill_mckee

    return np.asarray(
        reverse_cuthill_mckee(sp.tocsr(), symmetric_mode=False),
        dtype=np.int64,
    )


def would_build_dia(sp) -> bool:
    """SparseMatrix's DIA acceptance test (matrix.dia_gate) on host CSR."""
    sp = sp.tocsr()
    n = sp.shape[0]
    if sp.shape[0] != sp.shape[1] or sp.nnz == 0:
        return False
    rows = np.repeat(np.arange(n), np.diff(sp.indptr))
    offs = np.unique(sp.indices.astype(np.int64) - rows)
    return _m.dia_gate(offs.shape[0], n, sp.nnz)


def wants_reorder_scipy(sp) -> bool:
    """Is this host matrix in the slow zone (gather-bound) where a
    locality reordering could pay off on TPU?"""
    n = sp.shape[0]
    if sp.shape[0] != sp.shape[1] or n <= _m._DENSE_MAX_ROWS:
        return False
    return not would_build_dia(sp)


def reorder_coarse_level(P, R, Ac, dtype):
    """Renumber a freshly-built AMG coarse level for column locality.

    Coarse numbering is internal, so the RCM permutation is folded into
    P (columns) and R (rows) and never observable.  Applied only when
    the coarse operator sits in the gather-bound zone and the backend
    builds Pallas structures at all.
    """
    if not wants_reorder_scipy(Ac):
        return P, R, Ac
    if not _m._want_tiled_ell(np.dtype(dtype)):
        return P, R, Ac
    perm = rcm_permutation(Ac)
    Ac2 = Ac[perm][:, perm].tocsr()
    Ac2.sort_indices()
    P2 = P.tocsr()[:, perm].tocsr()
    P2.sort_indices()
    R2 = R.tocsr()[perm, :].tocsr()
    R2.sort_indices()
    return P2, R2, Ac2


def _walk_rows(A):
    """The kernel's walk over ``A``: touched x rows summed over its
    row tiles, or None when ``A`` has no windowed arrays."""
    if A.ell_wcount is None:
        return None
    return int(np.asarray(A.ell_wcount).sum())


def maybe_reorder(A, mode: str = "AUTO"):
    """Try an RCM renumbering of ``A``; returns ``(A2, perm)`` with
    ``A2 = A[perm][:, perm]`` or ``(A, None)`` when not worthwhile.

    AUTO adopts the ordering only when the permuted matrix gains a
    fast SpMV structure: DIA, or windowed ELL that the stored order
    lacks or walks at least twice as long.  RCM adopts it whenever the
    matrix is structurally eligible.  On backends that build no Pallas
    structures (CPU), AUTO never adopts.
    """
    mode = (mode or "AUTO").upper()
    if mode == "NONE":
        return A, None
    if (
        A.block_size != 1
        or not A.is_square
        or A.n_rows <= _m._DENSE_MAX_ROWS
        or A.has_dia
        or A.has_matrix_free
        or A.has_dense
    ):
        return A, None
    cur = _walk_rows(A)
    if mode == "AUTO":
        if not _m._want_tiled_ell(np.dtype(A.values.dtype)):
            return A, None
        # the kernel's cost is its walk: nothing to gain once each
        # tile touches only a few x rows
        if cur is not None and cur <= _GOOD_ROWS * A.ell_wcount.shape[0]:
            return A, None
    sp = A.to_scipy()
    perm = rcm_permutation(sp)
    sp2 = sp[perm][:, perm].tocsr()
    sp2.sort_indices()
    A2 = _m.SparseMatrix.from_scipy(sp2, dtype=np.dtype(A.values.dtype))
    if mode == "AUTO":
        new = _walk_rows(A2)
        gained = A2.has_dia or (
            new is not None and (cur is None or new * 2 <= cur)
        )
        if not gained:
            return A, None
    return A2, perm
