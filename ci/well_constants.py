"""Measure the windowed-ELL gate's cost constants on the chip.

    python ci/well_constants.py [--n 128] [--reps 20]

Sets up the classical benchmark configuration
(``benchmark/configs/poisson7_classical.json``, float32, 128^3 unless
``--n`` cuts it) with the host builder, then times one SpMV of each A, P
and R of the hierarchy that has ELL arrays in two ways: on the
windowed-ELL kernel over the matrix's tiling (taken whether or not the
gate admits it) and as the XLA gather over its plain ELL arrays.  Prints
one JSON line per matrix: shape, window, touched rows per tile, walk
steps (touched rows x slots, summed over tiles), both times, the
kernel's error against float64 scipy, and the gate's decision with the
constants in ``ops/pallas_well.py``.  The last line holds the fitted
constants, ``c_walk`` (kernel time less its window copies at the HBM
rate, per walk step) and ``c_gather`` (gather time per stored ELL
entry), each per matrix and over all matrices, and the hierarchy's
``well_matrices`` / ``well_touched_rows`` counters.  Needs a TPU.
"""

from __future__ import annotations

import argparse
import copy
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT]


def _timed(f, args, reps):
    """Seconds per call of ``f(*args)``, compiled and warmed first,
    with ``reps`` calls queued back to back; and the last result."""
    f(*args).block_until_ready()
    t0 = time.perf_counter()
    for _ in range(reps):
        y = f(*args)
    y.block_until_ready()
    return (time.perf_counter() - t0) / reps, y


def measure(name, sp, reps, interpret=False):
    """One line of the table for the scipy CSR matrix ``sp``."""
    import jax
    import jax.numpy as jnp

    from amgx_tpu.core.matrix import _build_ell_np
    from amgx_tpu.ops import pallas_well as pw
    from amgx_tpu.ops.spmv import ell_spmv_xla

    sp = sp.tocsr().astype(np.float32)
    sp.sort_indices()
    n, m = sp.shape
    ro = sp.indptr.astype(np.int64)
    w = int(np.diff(ro).max())
    cols, vals, _ = _build_ell_np(ro, sp.indices, sp.data, n, w, 1)
    x = np.random.default_rng(0).standard_normal(m).astype(np.float32)
    want = sp.astype(np.float64) @ x.astype(np.float64)
    xd = jnp.asarray(x)
    line = {"matrix": name, "rows": n, "cols": m, "slots": w,
            "entries": n * w}
    gather_s, _ = _timed(jax.jit(ell_spmv_xla),
                         (jnp.asarray(cols), jnp.asarray(vals), xd), reps)
    line["gather_ms"] = gather_s * 1e3
    line["c_gather_ns"] = gather_s / (n * w) * 1e9
    we = pw.tile_windowed_ell(ro, cols, vals)
    if we is None:
        line["window"] = "over the VMEM budget"
        return line
    copy_s = we.bases.shape[0] * we.width * 4 / pw._HBM_BYTES_PER_S
    steps = int(we.counts.sum()) * w
    arrays = tuple(jnp.asarray(a) for a in (we.cols, we.vals, we.bases,
                                            we.rows, we.counts))
    kernel_s, y = _timed(
        lambda *a: pw._pallas_well_spmv(*a, n, we.width,
                                        interpret=interpret),
        (*arrays, xd), reps)
    est_kernel, est_gather = pw.well_estimate_s(we, n)
    line.update(
        window=we.width, tiles=int(we.bases.shape[0]),
        touched_mean=float(we.counts.mean()),
        touched_max=int(we.counts.max()), steps=steps,
        kernel_ms=kernel_s * 1e3, copy_ms=copy_s * 1e3,
        c_walk_ns=(kernel_s - copy_s) / steps * 1e9,
        kernel_rel_err=float(np.abs(np.asarray(y, np.float64) - want).max()
                             / max(np.abs(want).max(), 1e-30)),
        admitted=bool(pw._KERNEL_MARGIN * est_kernel < est_gather),
    )
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--n", type=int, default=128)
    ap.add_argument("--reps", type=int, default=20)
    args = ap.parse_args(argv)

    import jax

    if jax.default_backend() != "tpu":
        print("well_constants: needs a TPU", file=sys.stderr)
        return 2

    from benchmark import generate, run

    import amgx_tpu
    from amgx_tpu.config.amg_config import AMGConfig
    from amgx_tpu.core.matrix import SparseMatrix
    from amgx_tpu.solvers import create_solver

    config = run.load_json(os.path.join(
        run.BENCH, "configs", "poisson7_classical.json"))
    config.update(nx=args.n, ny=args.n, nz=args.n)
    amgx_tpu.initialize()
    A = generate.operator(config, np.float32)
    cfg = copy.deepcopy(config["amgx_config"])
    cfg["solver"]["preconditioner"]["setup_location"] = "HOST"
    solver = create_solver(AMGConfig.from_string(json.dumps(cfg)),
                           "default")
    solver.setup(SparseMatrix.from_csr(A.indptr, A.indices, A.data,
                                       n_cols=A.shape[1]))
    lines = []
    for lvl in solver.precond.levels:
        for role in ("A", "P", "R"):
            M = getattr(lvl, role)
            if M is None or not M.has_ell or M.block_size != 1:
                continue
            line = measure(f"l{lvl.level_id}.{role}", M.to_scipy(),
                           args.reps)
            line["device"] = jax.devices()[0].device_kind
            print(json.dumps(line), flush=True)
            lines.append(line)
    timed = [ln for ln in lines if "kernel_ms" in ln]
    prof = solver.precond.setup_profile
    print(json.dumps({
        "c_walk_ns": {ln["matrix"]: ln["c_walk_ns"] for ln in timed},
        "c_walk_ns_all": sum(ln["kernel_ms"] - ln["copy_ms"] for ln in timed)
        / sum(ln["steps"] for ln in timed) * 1e6,
        "c_gather_ns": {ln["matrix"]: ln["c_gather_ns"] for ln in lines},
        "c_gather_ns_all": sum(ln["gather_ms"] for ln in lines)
        / sum(ln["entries"] for ln in lines) * 1e6,
        "well_matrices": prof.get("well_matrices"),
        "well_touched_rows": prof.get("well_touched_rows"),
        "device": jax.devices()[0].device_kind,
    }), flush=True)
    worst = max((ln["kernel_rel_err"] for ln in timed), default=0.0)
    return 0 if worst < 1e-4 else 1


if __name__ == "__main__":
    sys.exit(main())
