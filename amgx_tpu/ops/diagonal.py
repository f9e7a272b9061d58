"""Shared (block-)diagonal helpers for smoothers.

Zero-pivot policy: a zero diagonal entry gets reciprocal 1.0 (the
reference's zero_in_diagonal_handling behavior — solvers proceed, tests
zero_in_diagonal_handling.cu assert no crash).  Centralized so the policy
changes in one place.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np


def scalarized(A, solver_name: str, device: bool = True):
    """Scalar expansion of a block matrix (block rows/cols unrolled).

    Solvers without native block kernels operate on the expanded scalar
    operator — identical linear algebra, though block-coupled variants
    (e.g. block DILU) differ from their scalar expansions; native block
    paths are future work.  Vectors are flat (n*b,) either way, so no
    caller-visible change.  ``device=False`` builds the expansion
    host-resident (the AMG set-up defers it to the batched finalize
    transfer, preserving the one-batch-per-setup invariant)."""
    if A.block_size == 1:
        return A
    import warnings

    from amgx_tpu.core.matrix import SparseMatrix

    warnings.warn(
        f"{solver_name}: block_size {A.block_size} handled by scalar "
        "expansion (native block kernels TBD)"
    )
    sp = A.to_scipy()
    # the block expansion stores all b*b entries per block; drop explicit
    # zeros so the iteration operator (and colorings) keep the true graph
    sp.eliminate_zeros()
    return SparseMatrix.from_scipy(sp, device=device)


def invert_diag(A):
    """Inverse of the (block) diagonal, host-side at setup.

    Block policy (reference zero_in_diagonal_handling semantics,
    extended to blocks): an exactly-zero diagonal BLOCK scales by the
    identity, and any block whose inverse is singular/non-finite also
    falls back to identity — the smoother stays finite on that row
    instead of spraying inf/NaN through every sweep."""
    from amgx_tpu.core.profiling import setup_download, setup_upload

    d = setup_download(A.diag)
    if A.block_size == 1:
        with np.errstate(divide="ignore"):
            inv = np.where(d != 0, 1.0 / d, 1.0)
        # numpy promotes extension dtypes (bfloat16) against python
        # floats — the smoother state must stay in the LEVEL dtype or
        # every reduced-precision sweep silently upcasts
        return setup_upload(inv.astype(d.dtype, copy=False))
    b = A.block_size
    if d.dtype.itemsize < 4:
        # LAPACK has no sub-f32 factorizations (and numpy would hand
        # back f64): invert in f32, return in the level dtype
        d = d.astype(np.float32)
    eye = np.eye(b, dtype=d.dtype)
    zero = ~d.reshape(d.shape[0], -1).any(axis=1)
    safe = d.copy()
    safe[zero] = eye
    try:
        inv = np.linalg.inv(safe)
    except np.linalg.LinAlgError:
        # some non-zero block is exactly singular: invert per block
        inv = np.empty_like(safe)
        for i in range(safe.shape[0]):
            try:
                inv[i] = np.linalg.inv(safe[i])
            except np.linalg.LinAlgError:
                inv[i] = eye
    bad = ~np.all(
        np.isfinite(inv.reshape(inv.shape[0], -1)), axis=1
    )
    if bad.any():
        inv[bad] = eye
    return jnp.asarray(
        inv.astype(np.asarray(A.diag).dtype, copy=False)
    )


def invert_diag_jnp(A):
    """Traced twin of :func:`invert_diag` (same zero-pivot / singular-
    block identity policy) for values-only re-setup inside jit/vmap
    (serve batched params)."""
    d = A.diag
    if A.block_size == 1:
        return jnp.where(
            d != 0, 1.0 / jnp.where(d != 0, d, 1.0), 1.0
        ).astype(d.dtype)
    b = A.block_size
    out_dt = d.dtype
    if jnp.dtype(d.dtype).itemsize < 4:
        # jnp.linalg.inv has no sub-f32 kernel: invert in f32, return
        # in the level dtype (mirrors the host builder)
        d = d.astype(jnp.float32)
    eye = jnp.eye(b, dtype=d.dtype)
    zero = ~jnp.any(
        d.reshape(d.shape[0], -1) != 0, axis=1
    )
    safe = jnp.where(zero[:, None, None], eye, d)
    inv = jnp.linalg.inv(safe)
    bad = ~jnp.all(
        jnp.isfinite(inv.reshape(inv.shape[0], -1)), axis=1
    )
    return jnp.where(bad[:, None, None], eye, inv).astype(out_dt)


def apply_dinv(dinv, r, block_size):
    """z = D^{-1} r for flat vectors (block-aware)."""
    if block_size == 1:
        return dinv * r
    rb = r.reshape(-1, block_size)
    return jnp.einsum("nij,nj->ni", dinv, rb).reshape(-1)
