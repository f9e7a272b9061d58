#!/usr/bin/env python
"""SpMV correctness + throughput check (reference examples/amgx_spmv_test
analogue).

    python examples/spmv_test.py [file.mtx | N]     # default: 64^3 Poisson

Loads a MatrixMarket/%%NVAMGBinary file, or generates an N^3 7-pt
Poisson system, runs y = A x on the default backend, verifies against
the host product, and reports the marginal per-SpMV time (chain method:
the difference of two dependent chains, so dispatch and transfer cancel).
"""

import sys
import time

import numpy as np


def main(argv):
    import amgx_tpu

    amgx_tpu.initialize()
    import jax
    import jax.numpy as jnp

    from amgx_tpu.io.matrix_market import read_mtx
    from amgx_tpu.io.poisson import poisson_3d_7pt
    from amgx_tpu.ops.spmv import spmv

    arg = argv[1] if len(argv) > 1 else "64"
    if arg.isdigit():
        A = poisson_3d_7pt(int(arg), dtype=np.float32)
        label = f"poisson7 {arg}^3"
    else:
        A = read_mtx(arg, dtype=np.float32)
        label = arg
    n = A.n_rows
    rng = np.random.default_rng(0)
    x = rng.standard_normal(A.n_cols * A.block_size).astype(np.float32)

    y = np.asarray(spmv(A, jnp.asarray(x)))
    ref = A.to_scipy() @ x
    scale = max(float(np.abs(ref).max()), 1e-30)
    err = float(np.abs(y - ref).max()) / scale
    fmt = (
        "DIA" if A.has_dia else
        ("dense" if A.has_dense else
         (f"ELL+windowed(W={A.ell_wwidth})" if A.ell_wcols is not None
          else ("ELL" if A.has_ell else "CSR")))
    )

    def chain(iters):
        @jax.jit
        def f(A, x0):
            def body(i, v):
                return spmv(A, v) * np.float32(0.125) + x0
            return jax.lax.fori_loop(0, iters, body, x0)
        return f

    c1, c2 = chain(5), chain(55)
    xj = jnp.asarray(x)
    jax.device_get(c1(A, xj))
    jax.device_get(c2(A, xj))
    t1 = time.perf_counter()
    jax.device_get(c1(A, xj))
    t1 = time.perf_counter() - t1
    t2 = time.perf_counter()
    jax.device_get(c2(A, xj))
    t2 = time.perf_counter() - t2
    per = (t2 - t1) / 50
    gf = 2.0 * A.nnz * A.block_size ** 2 / max(per, 1e-12) / 1e9
    dev = jax.devices()[0]
    print(
        f"{label}: n={n} nnz={A.nnz} format={fmt} device={dev.platform}\n"
        f"max rel err vs host: {err:.2e}\n"
        f"marginal SpMV: {per * 1e6:.1f} us  ({gf:.1f} GFLOPS)"
    )
    return 0 if err < 1e-5 else 1


if __name__ == "__main__":
    raise SystemExit(main(sys.argv))
