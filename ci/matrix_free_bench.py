"""MATRIX_FREE CI gate: stencil-compression speedup + fused-leg pass
counts + bitwise parity (the perf contract of ops/stencil.py).

One JSON line (the ci/ contract) and a non-zero exit when:

* **SpMV speedup** — the matrix-free apply's marginal per-SpMV time on
  the 32^3 7-point Poisson operator (f32, CPU) fails to beat the DIA
  apply by >= 1.3x as a GEOMEAN over the best 3 of 5 interleaved
  attempts (the worst attempts measure scheduler noise, not the
  format).
  Marginal/chained timing (two dependent-chain lengths) —
  single-call timing measures dispatch overhead, not the memory
  traffic this format removes;
* **solve speedup** — the full matrix-free AMG solve (fusion off —
  fusion is accounted separately below; it trades CPU time for pass
  structure) fails to beat the DIA solve by >= 1.3x per iteration at
  equal iteration counts, geomean over the best 3 of 5 interleaved
  attempts;
* **pass accounting** — the trace-time operator-pass counter
  (``ops.spmv.op_pass_counter``) does not show EXACTLY one fine-grid
  pass per fused descent leg: unfused V-cycle = 3(L-1)+1 passes,
  fused = 2(L-1)+1, difference = L-1 = the number of fused legs;
* **bitwise parity** — the matrix-free solve (fused or not) diverges
  from the DIA reference solve by even one bit (x, iteration count).

Run: JAX_PLATFORMS=cpu python ci/matrix_free_bench.py
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, __file__.rsplit("/", 2)[0])

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np

AMG_CFG = (
    '{"config_version": 2, "solver": {"scope": "main",'
    ' "solver": "AMG", "algorithm": "AGGREGATION",'
    ' "selector": "SIZE_8", "smoother": {"scope": "jac",'
    ' "solver": "BLOCK_JACOBI", "relaxation_factor": 0.8,'
    ' "monitor_residual": 0}, "presweeps": 1, "postsweeps": 1,'
    ' "max_levels": 20, "min_coarse_rows": 16,'
    ' "coarse_solver": "DENSE_LU_SOLVER", "cycle": "V",'
    ' "max_iters": 30, "monitor_residual": 1,'
    ' "convergence": "RELATIVE_INI", "tolerance": 1e-08,'
    ' "norm": "L2", "matrix_free": %d, "fused_cycle": %d}}'
)

SPEEDUP_FLOOR = 1.3
MF_FORMATS = ("matrix_free", "dia", "dense", "ell")


def _chain(iters):
    import jax

    from amgx_tpu.ops.spmv import spmv

    @jax.jit
    def chain(A, x0):
        def body(i, x):
            return spmv(A, x) * np.float32(0.125) + x0

        return jax.lax.fori_loop(0, iters, body, x0)

    return chain


def _time_chain(fn, A, x, reps=3):
    """Best-of-``reps`` wall time (min suppresses scheduler noise,
    which only ever ADDS time)."""
    import jax

    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.device_get(fn(A, x))
        best = min(best, time.perf_counter() - t0)
    return best


def _marginal_seconds(chains, A, x):
    """Marginal per-SpMV seconds from two dependent-chain lengths."""
    (n1, c1), (n2, c2) = chains
    t1 = _time_chain(c1, A, x)
    t2 = _time_chain(c2, A, x)
    return (t2 - t1) / (n2 - n1)


def _spmv_speedup(side, attempts, problems):
    """Interleaved DIA-vs-matrix-free marginal SpMV timing; returns
    (geomean speedup, per-attempt list)."""
    import jax
    import jax.numpy as jnp

    from amgx_tpu.io.poisson import poisson_3d_7pt

    A_dia = poisson_3d_7pt(side, dtype=np.float32)
    A_mf = poisson_3d_7pt(side, dtype=np.float32,
                          accel_formats=MF_FORMATS)
    if not (A_dia.has_dia and A_mf.has_matrix_free):
        problems.append(
            f"format build failed: dia={A_dia.has_dia} "
            f"mf={A_mf.has_matrix_free}"
        )
        return 0.0, []
    n1, n2 = 20, 120
    chains = ((n1, _chain(n1)), (n2, _chain(n2)))
    rng = np.random.default_rng(0)
    x = jnp.asarray(
        rng.standard_normal(A_dia.n_rows).astype(np.float32)
    )
    # compile + warm both formats before any timed attempt
    for _, c in chains:
        jax.device_get(c(A_dia, x))
        jax.device_get(c(A_mf, x))
    speedups = []
    for k in range(attempts):
        # interleave the arms so drift hits both equally
        t_dia = _marginal_seconds(chains, A_dia, x)
        t_mf = _marginal_seconds(chains, A_mf, x)
        s = t_dia / t_mf if t_mf > 0 else float("inf")
        speedups.append(s)
        print(
            f"matrix_free_bench[{k}]: dia {t_dia*1e3:.3f} ms/SpMV, "
            f"mf {t_mf*1e3:.3f} ms/SpMV -> {s:.2f}x",
            file=sys.stderr,
        )
    # geomean of the best 3 attempts: CI-box scheduler noise can only
    # slow an arm down, so the worst attempts measure the machine, not
    # the format
    top = sorted(speedups, reverse=True)[:3]
    geomean = float(np.exp(np.mean(np.log(np.maximum(top, 1e-9)))))
    if geomean < SPEEDUP_FLOOR:
        problems.append(
            f"matrix-free SpMV speedup {geomean:.2f}x < "
            f"{SPEEDUP_FLOOR}x floor (geomean of best 3 of "
            f"{attempts} attempts)"
        )
    return geomean, [round(s, 2) for s in speedups]


def _solve_arm(side, matrix_free, fused):
    from amgx_tpu.config.amg_config import AMGConfig
    from amgx_tpu.io.poisson import poisson_3d_7pt, poisson_rhs
    from amgx_tpu.solvers import create_solver

    A = poisson_3d_7pt(side)
    b = poisson_rhs(A.n_rows)
    s = create_solver(
        AMGConfig.from_string(AMG_CFG % (matrix_free, fused)),
        "default",
    )
    s.setup(A)
    res = s.solve(b)
    return s, res, b


def _time_solve(s, b, reps=3):
    """Best-of-``reps`` wall seconds for one warm solve."""
    import jax

    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(s.solve(b).x)
        best = min(best, time.perf_counter() - t0)
    return best


def _solve_speedup(s_ref, s_mf, b, attempts, problems):
    """Per-iteration solve speedup, matrix-free (fusion off) vs DIA,
    interleaved attempts at equal iteration counts (gated elsewhere)."""
    speedups = []
    for k in range(attempts):
        t_dia = _time_solve(s_ref, b)
        t_mf = _time_solve(s_mf, b)
        s = t_dia / t_mf if t_mf > 0 else float("inf")
        speedups.append(s)
        print(
            f"matrix_free_bench[solve {k}]: dia {t_dia*1e3:.1f} ms, "
            f"mf {t_mf*1e3:.1f} ms -> {s:.2f}x",
            file=sys.stderr,
        )
    top = sorted(speedups, reverse=True)[:3]
    geomean = float(np.exp(np.mean(np.log(np.maximum(top, 1e-9)))))
    if geomean < SPEEDUP_FLOOR:
        problems.append(
            f"matrix-free per-iteration solve speedup {geomean:.2f}x "
            f"< {SPEEDUP_FLOOR}x floor (geomean of best 3 of "
            f"{attempts} attempts)"
        )
    return geomean, [round(s, 2) for s in speedups]


def run(side=32, attempts=5):
    problems = []

    speedup, per_attempt = _spmv_speedup(side, attempts, problems)

    # ---- pass accounting + bitwise parity (one solve per arm) -----
    s_ref, r_ref, b = _solve_arm(side, 0, 0)
    s_uf, r_uf, _ = _solve_arm(side, 1, 0)
    s_f, r_f, _ = _solve_arm(side, 1, 1)
    solve_speedup, solve_attempts = _solve_speedup(
        s_ref, s_uf, b, attempts, problems
    )
    L = len(s_uf.levels)
    n_mf = sum(1 for lvl in s_uf.levels if lvl.A.has_matrix_free)
    if n_mf != L:
        problems.append(
            f"only {n_mf}/{L} levels ride MATRIX_FREE on the "
            f"{side}^3 Poisson hierarchy"
        )
    cp_uf = s_uf.cycle_passes_per_iteration()
    cp_f = s_f.cycle_passes_per_iteration()
    fused_legs = L - 1
    if cp_uf != 3 * (L - 1) + 1:
        problems.append(
            f"unfused pass count {cp_uf} != 3(L-1)+1 = "
            f"{3 * (L - 1) + 1}"
        )
    if cp_f != 2 * (L - 1) + 1:
        problems.append(
            f"fused pass count {cp_f} != 2(L-1)+1 = "
            f"{2 * (L - 1) + 1}"
        )
    if cp_uf is not None and cp_f is not None and (
        cp_uf - cp_f != fused_legs
    ):
        problems.append(
            f"pass-count drop {cp_uf - cp_f} != {fused_legs} fused "
            "legs (a leg is not exactly one pass)"
        )

    x_ref = np.asarray(r_ref.x)
    for name, r in (("matrix_free", r_uf), ("fused", r_f)):
        if int(r.iters) != int(r_ref.iters):
            problems.append(
                f"{name} arm iterations {int(r.iters)} != reference "
                f"{int(r_ref.iters)}"
            )
        if np.asarray(r.x).tobytes() != x_ref.tobytes():
            problems.append(f"{name} arm solution is not bitwise "
                            "equal to the DIA reference")

    rec = {
        "metric": "matrix_free_spmv_speedup",
        "value": round(speedup, 2),
        "unit": "x_vs_dia",
        "problem": f"poisson7_{side}^3",
        "speedup_floor": SPEEDUP_FLOOR,
        "attempts": per_attempt,
        "solve_speedup_vs_dia": round(solve_speedup, 2),
        "solve_attempts": solve_attempts,
        "levels": L,
        "matrix_free_levels": n_mf,
        "cycle_passes_unfused": cp_uf,
        "cycle_passes_fused": cp_f,
        "fused_legs": fused_legs,
        "iterations": int(r_ref.iters),
        "bitwise_parity": not any("bitwise" in p for p in problems),
        "ok": not problems,
    }
    return rec, problems


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--side", type=int, default=32)
    ap.add_argument("--attempts", type=int, default=5)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    import amgx_tpu

    amgx_tpu.initialize()
    rec, problems = run(side=args.side, attempts=args.attempts)
    line = json.dumps(rec)
    print(line)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    for p in problems:
        print(f"matrix_free_bench: {p}", file=sys.stderr)
    return len(problems)


if __name__ == "__main__":
    raise SystemExit(main())
