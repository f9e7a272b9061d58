"""A plain classical Ruge-Stueben AMG in float64 numpy/scipy, written from
the published description, to check the program's classical set-up and
V-cycle against.  It shares no code with ``amgx_tpu``; a caller hands it
the operator, the configured numbers and the random numbers PMIS uses.

- Strength (AHAT, Ruge and Stueben 1987; Stueben, "An introduction to
  algebraic multigrid", 2001, section A.2.1): j strongly influences i iff
  ``-a_ij >= theta * max_{k != i} (-a_ik)`` with ``-a_ij > 0``.  A row with
  no negative off-diagonal measures ``|a_ij|`` against
  ``theta * max_{k != i} |a_ik|`` instead.  ``max_row_sum`` at or above 1
  weakens nothing, so it has no part here.
- PMIS (De Sterck, Yang and Heys, SIAM J. Matrix Anal. Appl. 27, 2006): a
  point's measure is the number of points it strongly influences plus a
  random number in [0, 1).  Each round, an undecided point whose measure
  exceeds that of every undecided strong neighbour becomes C, and then
  every undecided point with a strong C neighbour becomes F.  Neighbours
  are taken in the symmetrised strength graph ``S + S^T``, in both steps,
  as AmgX's selectors/pmis.cu takes them (the paper's F step follows only
  the points that depend on a new C point).  A point with no strong
  connection at all has nothing to interpolate from and is C; a point
  still undecided when no round changes anything is C.

  The one departure from the published algorithm: the random numbers are
  an input, not drawn here.  The program draws them from a hash of the
  row index, and with the same numbers the same split must come out.
- Direct (distance-1) interpolation (Stueben 2001, section A.7.2.1): a C
  point keeps its value; an F point i takes
  ``w_ij = -alpha_i a_ij / a~_ii`` from each strong C neighbour j with
  ``a_ij < 0`` and ``-beta_i a_ij / a~_ii`` with ``a_ij > 0``, where
  ``alpha_i`` (``beta_i``) is the sum of the row's negative (positive)
  off-diagonals over that of its strong C neighbours, and ``a~_ii`` is
  ``a_ii`` plus the positive off-diagonals when no strong C neighbour is
  positive.
- Galerkin coarse operator ``Ac = R A P`` with ``R = P^T``.
- One V(pre, post) cycle from x = 0 with damped Jacobi, ``x += omega
  D^{-1} (b - A x)``, and an exact dense solve on the coarsest level.
"""

from __future__ import annotations

import numpy as np
import scipy.sparse as sps


def _rows(A):
    """``(i, [(j, a_ij) for each stored off-diagonal j], a_ii)``."""
    A = sps.csr_matrix(A, dtype=np.float64)
    for i in range(A.shape[0]):
        lo, hi = A.indptr[i], A.indptr[i + 1]
        cols, vals = A.indices[lo:hi], A.data[lo:hi]
        off = [(int(j), float(a)) for j, a in zip(cols, vals) if j != i]
        yield i, off, float(vals[cols == i].sum())


def strength(A, theta: float) -> list:
    """``S[i]``: the set of points that strongly influence i."""
    S = []
    for _, off, _ in _rows(A):
        neg = [-a for _, a in off if a < 0]
        if neg:
            bound = theta * max(neg)
            S.append({j for j, a in off if -a > 0 and -a >= bound})
        else:
            bound = theta * max((abs(a) for _, a in off), default=0.0)
            S.append({j for j, a in off if abs(a) > 0 and abs(a) >= bound})
    return S


def pmis(S: list, rand: np.ndarray) -> np.ndarray:
    """C/F split, 1 for C and 0 for F, from the strength sets and the
    random numbers ``rand`` (one in [0, 1) a point)."""
    n = len(S)
    influences = [0] * n
    neighbours = [set(s) for s in S]
    for i, s in enumerate(S):
        for j in s:
            influences[j] += 1
            neighbours[j].add(i)
    measure = [influences[i] + float(rand[i]) for i in range(n)]
    C, F = 1, -1
    state = [C if not neighbours[i] else 0 for i in range(n)]
    while True:
        undecided = [i for i in range(n) if state[i] == 0]
        new_c = [i for i in undecided
                 if all(measure[i] > measure[j] for j in neighbours[i]
                        if state[j] == 0)]
        for i in new_c:
            state[i] = C
        new_f = [i for i in undecided if state[i] == 0
                 and any(state[j] == C for j in neighbours[i])]
        for i in new_f:
            state[i] = F
        if not new_c and not new_f:
            break
    return np.array([1 if s != F else 0 for s in state], np.int8)


def direct_interpolation(A, S: list, cf: np.ndarray) -> sps.csr_matrix:
    """P, n x (number of C points)."""
    n = A.shape[0]
    coarse = np.cumsum(cf) - 1
    rows, cols, vals = [], [], []
    for i, off, diag in _rows(A):
        if cf[i]:
            rows.append(i)
            cols.append(int(coarse[i]))
            vals.append(1.0)
            continue
        strong_c = [(j, a) for j, a in off if j in S[i] and cf[j]]
        neg_all = sum(a for _, a in off if a < 0)
        pos_all = sum(a for _, a in off if a > 0)
        neg_c = sum(a for _, a in strong_c if a < 0)
        pos_c = sum(a for _, a in strong_c if a > 0)
        alpha = neg_all / neg_c if neg_c != 0 else 0.0
        beta = pos_all / pos_c if pos_c != 0 else 0.0
        d = diag + (pos_all if pos_c == 0 else 0.0)
        for j, a in strong_c:
            rows.append(i)
            cols.append(int(coarse[j]))
            vals.append(-(alpha if a < 0 else beta) * a / d)
    return sps.csr_matrix((vals, (rows, cols)),
                          shape=(n, int(cf.sum())))


def coarsen(A, theta: float, rand):
    """One level: ``(S, cf, P, R, Ac)``."""
    A = sps.csr_matrix(A, dtype=np.float64)
    S = strength(A, theta)
    cf = pmis(S, rand)
    P = direct_interpolation(A, S, cf)
    R = P.T.tocsr()
    return S, cf, P, R, (R @ A @ P).tocsr()


def hierarchy(A, theta: float, rand_of, max_levels: int,
              coarse_rows: int) -> list:
    """Levels ``[{"A", "cf", "P", "R"}]`` down to the coarsest, whose
    entry holds only ``A``.  Coarsening stops at ``max_levels`` levels, at
    a level of ``coarse_rows`` rows or fewer, or when a level does not
    shrink.  ``rand_of(n)`` gives the random numbers of a level of n
    points."""
    levels = [{"A": sps.csr_matrix(A, dtype=np.float64)}]
    while len(levels) < max_levels:
        A = levels[-1]["A"]
        n = A.shape[0]
        if n <= coarse_rows:
            break
        _, cf, P, R, Ac = coarsen(A, theta, rand_of(n))
        if not 0 < Ac.shape[0] < n:
            break
        levels[-1].update(cf=cf, P=P, R=R)
        levels.append({"A": Ac})
    return levels


def v_cycle(levels: list, b: np.ndarray, omega: float, pre: int = 1,
            post: int = 1) -> np.ndarray:
    """One V(pre, post) cycle from x = 0: ``levels`` as
    :func:`hierarchy` gives them (``A``, ``P``, ``R`` on every level but
    the coarsest, which is solved exactly)."""

    def cycle(k, b):
        A = sps.csr_matrix(levels[k]["A"], dtype=np.float64)
        if k == len(levels) - 1:
            return np.linalg.solve(A.toarray(), b)
        d = A.diagonal()
        x = np.zeros_like(b)
        for _ in range(pre):
            x = x + omega * (b - A @ x) / d
        r = b - A @ x
        xc = cycle(k + 1, levels[k]["R"].astype(np.float64) @ r)
        x = x + levels[k]["P"].astype(np.float64) @ xc
        for _ in range(post):
            x = x + omega * (b - A @ x) / d
        return x

    return cycle(0, np.asarray(b, np.float64))
