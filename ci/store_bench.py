"""Setup-artifact store benchmark: cold setup vs ``load_setup``
restore vs warm-boot service start.

Prints ONE JSON line (same contract as ci/serve_bench.py):
``{"metric": "store_restore_speedup", "value": <x>, ...}`` — value is
the geometric mean over the Poisson suite of

    (cold hierarchy setup seconds) / (load_setup restore seconds)

with a floor check (``--floor``, default 3.0): a restore that isn't
several times faster than setup means the store stopped paying for
itself and CI fails.  Alongside it the record carries the warm-boot
serving scenario end to end: service A (with a store) builds and
exports a hierarchy, a FRESH service B warm-boots from the same store
and must serve its first group for the persisted fingerprint as a
cache HIT (``warmboot_cache_hits`` >= 1, ``warmboot_cache_misses``
== 0) — the PR 4 acceptance contract, enforced here and in
tests/test_store.py.

Run on the CPU backend (the tier the acceptance gate measures):

    JAX_PLATFORMS=cpu python ci/store_bench.py [--out FILE]

Methodology: best-of-``reps`` for both sides (same treatment, so
neither side eats the other's warm-up noise); setup includes solver
creation, restore includes payload read + rehydration + smoother/LU
re-derivation.  Restored solvers are verified to reproduce the
original iteration count before any timing is reported — a fast wrong
restore must fail the bench, not win it.
"""

import argparse
import json
import sys
import tempfile
import time

# runnable from any cwd: the repo root precedes ci/ on the path
sys.path.insert(0, __file__.rsplit("/", 2)[0])

PCG_AMG = """
{"config_version": 2,
 "solver": {"scope": "main", "solver": "PCG", "max_iters": 100,
    "tolerance": 1e-8, "monitor_residual": 1,
    "convergence": "RELATIVE_INI",
    "preconditioner": {"scope": "amg", "solver": "AMG",
       "algorithm": "CLASSICAL", "selector": "PMIS",
       "smoother": {"scope": "jac", "solver": "BLOCK_JACOBI",
           "relaxation_factor": 0.8, "monitor_residual": 0},
       "presweeps": 1, "postsweeps": 1, "max_levels": 20,
       "min_coarse_rows": 16, "coarse_solver": "DENSE_LU_SOLVER",
       "cycle": "V", "max_iters": 1, "monitor_residual": 0}}}
"""


def _poisson_suite():
    from amgx_tpu.io.poisson import poisson_2d_5pt, poisson_3d_27pt

    return [
        ("poisson2d-256", lambda: poisson_2d_5pt(256)),
        ("poisson3d-24-27pt", lambda: poisson_3d_27pt(24)),
    ]


def _time_case(A, reps):
    import os

    import numpy as np

    from amgx_tpu.config.amg_config import AMGConfig
    from amgx_tpu.io.poisson import poisson_rhs
    from amgx_tpu.solvers import create_solver
    from amgx_tpu.solvers.base import Solver

    cfg = AMGConfig.from_string(PCG_AMG)
    b = poisson_rhs(A.n_rows, dtype=np.asarray(A.values).dtype)
    t_setup = float("inf")
    solver = None
    for _ in range(reps):
        s = create_solver(cfg, "default")
        t0 = time.perf_counter()
        s.setup(A)
        t_setup = min(t_setup, time.perf_counter() - t0)
        solver = s
    res_ref = solver.solve(b)

    with tempfile.TemporaryDirectory(prefix="amgx_store_bench_") as d:
        path = os.path.join(d, "setup.npz")
        t0 = time.perf_counter()
        solver.save_setup(path)
        t_save = time.perf_counter() - t0
        payload_mb = os.path.getsize(path) / 2**20
        t_load = float("inf")
        restored = None
        for _ in range(reps):
            t0 = time.perf_counter()
            restored = Solver.load_setup(path)
            t_load = min(t_load, time.perf_counter() - t0)
    # correctness gate BEFORE the speedup means anything
    res2 = restored.solve(b)
    amg = restored.precond
    if (
        int(res2.iters) != int(res_ref.iters)
        or int(res2.status) != int(res_ref.status)
        or amg.setup_stats["coarsen_calls"] != 0
    ):
        raise RuntimeError(
            f"restore mismatch: iters {int(res_ref.iters)} -> "
            f"{int(res2.iters)}, status {int(res_ref.status)} -> "
            f"{int(res2.status)}, coarsen_calls "
            f"{amg.setup_stats['coarsen_calls']}"
        )
    return {
        "n": A.n_rows,
        "nnz": A.nnz,
        "setup_s": round(t_setup, 4),
        "save_s": round(t_save, 4),
        "restore_s": round(t_load, 4),
        "payload_mb": round(payload_mb, 2),
        "speedup": round(t_setup / t_load, 2),
        "iters": int(res_ref.iters),
    }


def _warmboot_case():
    """End-to-end warm-boot serving: export from service A, boot
    service B from the store, first group must be a hierarchy-cache
    hit."""
    import shutil

    from amgx_tpu.io.poisson import jittered_poisson_family
    from amgx_tpu.serve import BatchedSolveService

    root = tempfile.mkdtemp(prefix="amgx_store_bench_wb_")
    try:
        systems = jittered_poisson_family((32, 32), 8, seed=0)
        svc1 = BatchedSolveService(max_batch=8, store=root)
        svc1.solve_many(systems)
        svc1.flush_store()

        t0 = time.perf_counter()
        svc2 = BatchedSolveService(max_batch=8, store=root)
        restored = svc2.warm_boot()
        t_boot = time.perf_counter() - t0
        svc2.solve_many(systems)
        m = svc2.metrics.snapshot()
        return {
            "restored_entries": restored,
            "boot_s": round(t_boot, 4),
            "warmboot_cache_hits": m.get("cache_hits", 0),
            "warmboot_cache_misses": m.get("cache_misses", 0),
            "warmboot_setups": m.get("setups", 0),
        }
    finally:
        shutil.rmtree(root, ignore_errors=True)


def run(reps: int = 3):
    import amgx_tpu

    amgx_tpu.initialize()
    cases = {}
    for name, make in _poisson_suite():
        cases[name] = _time_case(make(), reps)
    speedups = [c["speedup"] for c in cases.values()]
    geo = 1.0
    for s in speedups:
        geo *= s
    geo = geo ** (1.0 / len(speedups))
    rec = {
        "metric": "store_restore_speedup",
        "value": round(geo, 2),
        "unit": "x (cold setup / restore)",
        "cases": cases,
    }
    rec.update(_warmboot_case())
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--floor", type=float, default=3.0)
    args = ap.parse_args()

    rec = run(reps=args.reps)
    rec["floor"] = args.floor
    failures = []
    if rec["value"] < args.floor:
        failures.append(
            f"restore_speedup {rec['value']} < floor {args.floor}"
        )
    if rec["warmboot_cache_hits"] < 1 or rec["warmboot_cache_misses"]:
        failures.append(
            "warm-boot service did not serve its first group from the "
            f"store (hits={rec['warmboot_cache_hits']}, "
            f"misses={rec['warmboot_cache_misses']})"
        )
    rec["pass"] = not failures
    line = json.dumps(rec)
    print(line)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    if failures:
        print("store_bench FAILED: " + "; ".join(failures),
              file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
