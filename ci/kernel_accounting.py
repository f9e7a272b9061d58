"""Interpret-mode DMA / VMEM byte accounting for the Pallas kernels
(VERDICT r3 #1 fallback evidence: when no TPU window opens, commit the
per-kernel traffic model alongside the timestamped failed probes).

Runs both kernels in interpret mode on benchmark-scale operands,
validates numerics against the XLA reference, and prints the DMA
traffic (HBM bytes moved per SpMV, from the grid x BlockSpec shapes)
plus the per-grid-step VMEM working set — the quantities that bound
the kernels' achievable fraction of HBM bandwidth once hardware is
reachable.

Usage: python ci/kernel_accounting.py [--n 96]
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402


def dia_accounting(n_side: int):
    from amgx_tpu.io.poisson import poisson_3d_7pt
    from amgx_tpu.ops import pallas_dia as pd

    A = poisson_3d_7pt(n_side, dtype=np.float32)
    assert A.has_dia
    n = A.n_rows
    offsets = tuple(int(o) for o in A.dia_offsets)
    nd = len(offsets)
    item = 4  # f32
    halo_lo = pd._pad_up(max(0, -min(offsets)), pd._LANE)
    halo_hi = pd._pad_up(max(0, max(offsets)), pd._LANE)
    r_cap = max(
        1024, pd._VALS_VMEM_BUDGET // (8 * nd) // 1024 * 1024
    )
    R = min(pd._ROW_BLOCK, r_cap, pd._pad_up(n, 1024))
    m = R // pd._LANE
    nt = -(-n // R)
    mwin = pd._pad_up((R + halo_lo + halo_hi) // pd._LANE + 1, 8)

    # numerics: interpret-mode kernel vs dense reference on a slice
    dv = jnp.asarray(np.asarray(A.dia_vals, dtype=np.float32))
    x = jnp.asarray(
        np.random.default_rng(0).standard_normal(n).astype(np.float32)
    )
    y = np.asarray(
        pd._pallas_dia_spmv(dv, x, offsets, n, interpret=True)
    )
    ref = np.asarray(A.to_scipy() @ np.asarray(x))
    ok = np.allclose(y, ref, rtol=1e-4, atol=1e-4)

    vals_bytes = nd * nt * m * pd._LANE * item  # one pass over values
    x_bytes = nt * mwin * pd._LANE * item      # windowed x DMA per tile
    out_bytes = nt * m * pd._LANE * item
    vmem = (nd * m + mwin + m) * pd._LANE * item
    return dict(
        kernel="pallas_dia",
        n=n,
        interpret_ok=bool(ok),
        grid_tiles=nt,
        dma_bytes_per_spmv=int(vals_bytes + x_bytes + out_bytes),
        dma_vals_bytes=int(vals_bytes),
        dma_x_window_bytes=int(x_bytes),
        dma_out_bytes=int(out_bytes),
        vmem_working_set_bytes=int(vmem),
        flops=int(2 * A.nnz),
        arithmetic_intensity=round(
            2 * A.nnz / (vals_bytes + x_bytes + out_bytes), 3
        ),
    )


def well_accounting(n_side: int):
    from amgx_tpu.io.poisson import poisson_3d_7pt
    from amgx_tpu.ops import pallas_well as pw

    sp = poisson_3d_7pt(n_side, dtype=np.float32).to_scipy().tocsr()
    n = sp.shape[0]
    lens = np.diff(sp.indptr)
    w = int(lens.max())
    cols = np.zeros((n, w), np.int32)
    vals = np.zeros((n, w), np.float32)
    r = np.repeat(np.arange(n), lens)
    pos = np.arange(sp.nnz) - sp.indptr[r]
    cols[r, pos] = sp.indices
    vals[r, pos] = sp.data
    built = pw.tile_windowed_ell(sp.indptr, cols, vals)
    assert built is not None, "no bounded window for this matrix"
    tc, tv, bs, W = built.cols, built.vals, built.bases, built.width
    nt = tc.shape[0]
    item = 4
    x = np.random.default_rng(0).standard_normal(n).astype(np.float32)
    y = np.asarray(
        pw._pallas_well_spmv(
            jnp.asarray(tc), jnp.asarray(tv), jnp.asarray(bs),
            jnp.asarray(built.rows), jnp.asarray(built.counts),
            jnp.asarray(x), n, W, interpret=True,
        )
    )
    ref = sp @ x
    ok = np.allclose(y[:n], ref, rtol=1e-4, atol=1e-4)

    cols_bytes = tc.size * item
    vals_bytes = tv.size * item
    xwin_bytes = nt * W * item  # one x window DMA per tile
    out_bytes = nt * pw._ROW_TILE * item
    vmem = (
        tc.size // nt + tv.size // nt + W + pw._ROW_TILE
    ) * item
    return dict(
        kernel="pallas_well",
        n=n,
        window_lanes=int(W),
        interpret_ok=bool(ok),
        grid_tiles=int(nt),
        dma_bytes_per_spmv=int(
            cols_bytes + vals_bytes + xwin_bytes + out_bytes
        ),
        dma_cols_bytes=int(cols_bytes),
        dma_vals_bytes=int(vals_bytes),
        dma_x_window_bytes=int(xwin_bytes),
        dma_out_bytes=int(out_bytes),
        vmem_working_set_bytes=int(vmem),
        flops=int(2 * sp.nnz),
        arithmetic_intensity=round(
            2 * sp.nnz
            / (cols_bytes + vals_bytes + xwin_bytes + out_bytes),
            3,
        ),
    )


def main():
    import json

    n_side = 96
    if "--n" in sys.argv:
        n_side = int(sys.argv[sys.argv.index("--n") + 1])
    for rec in (dia_accounting(n_side), well_accounting(min(n_side, 48))):
        print(json.dumps(rec))
        assert rec["interpret_ok"], rec


if __name__ == "__main__":
    main()
