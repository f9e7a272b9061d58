"""Operator ``poisson7``: the 7-point constant-coefficient Poisson matrix of
AmgX's ``AMGX_generate_distributed_poisson_7pt``
(``examples/amgx_mpi_poisson7.c``): 6 on the diagonal, -1 to each of the six
grid neighbours, Dirichlet boundaries, rows numbered ``x + nx * (y + ny * z)``.
It is built here as CSR arrays in one vectorised pass (columns sorted within
each row), so the benchmark owns its inputs: the program receives only these
arrays, and the same matrix in float64 is the plain reference.

The configuration gives the grid as ``nx``, ``ny``, ``nz``.
"""

from __future__ import annotations

import numpy as np
import scipy.sparse as sps


def poisson7_csr(nx: int, ny: int, nz: int, dtype=np.float64):
    """``(indptr, indices, data)`` of the 7-point operator on an
    ``nx x ny x nz`` grid."""
    n = nx * ny * nz
    if n >= 2**31:
        raise ValueError(f"{n} rows do not fit int32 column indices")
    row = np.arange(n, dtype=np.int32)
    x = row % nx
    y = (row // nx) % ny
    z = row // (nx * ny)
    # ascending column offsets, so the row-major mask keeps rows sorted
    offsets = np.array([-nx * ny, -nx, -1, 0, 1, nx, nx * ny], np.int32)
    valid = np.stack(
        [z > 0, y > 0, x > 0, np.ones(n, bool), x < nx - 1, y < ny - 1,
         z < nz - 1],
        axis=1,
    )
    del x, y, z
    indices = (row[:, None] + offsets)[valid]
    stencil = np.array([-1, -1, -1, 6, -1, -1, -1], dtype)
    data = np.broadcast_to(stencil, valid.shape)[valid]
    indptr = np.zeros(n + 1, np.int64)
    np.cumsum(valid.sum(axis=1), out=indptr[1:])
    if indptr[-1] < 2**31:
        indptr = indptr.astype(np.int32)
    return indptr, indices, data


def build(config: dict, dtype=np.float64) -> sps.csr_matrix:
    """The configuration's grid as a scipy CSR matrix."""
    nx, ny, nz = (int(config[k]) for k in ("nx", "ny", "nz"))
    n = nx * ny * nz
    indptr, indices, data = poisson7_csr(nx, ny, nz, dtype)
    return sps.csr_matrix((data, indices, indptr), shape=(n, n))
