"""Classical-setup placement profile (VERDICT r4 #1 'Done' criterion).

Runs the classical PMIS+D1 hierarchy setup on a 3D Poisson problem with
setup_location=DEVICE and =HOST and prints a JSON line per run:
total setup seconds, the set-up's host / device / transfer / compile
split, scalar sync count, level count, and iteration parity of a PCG
solve.

Usage: python ci/setup_profile.py [n_side] [--solve]
"""

import json
import sys
import time

import numpy as np

sys.path.insert(0, __file__.rsplit("/", 2)[0])


def main():
    import os

    # CPU unless the caller names another backend via
    # AMGX_TPU_PROFILE_PLATFORM
    os.environ["JAX_PLATFORMS"] = os.environ.get(
        "AMGX_TPU_PROFILE_PLATFORM", "cpu"
    )
    import jax

    jax.config.update("jax_enable_x64", True)
    import amgx_tpu

    amgx_tpu.initialize()
    from amgx_tpu.config.amg_config import AMGConfig
    from amgx_tpu.io.poisson import poisson_3d_7pt, poisson_rhs
    from amgx_tpu.solvers import create_solver

    n_side = int(sys.argv[1]) if len(sys.argv) > 1 else 96
    do_solve = "--solve" in sys.argv
    A = poisson_3d_7pt(n_side, dtype=np.float64)
    b = poisson_rhs(A.n_rows, dtype=np.float64)
    cfg_s = (
        '{"config_version": 2, "solver": {"scope": "main", '
        '"solver": "PCG", "max_iters": 100, "tolerance": 1e-8, '
        '"convergence": "RELATIVE_INI", "monitor_residual": 1, '
        '"preconditioner": {"scope": "amg", "solver": "AMG", '
        '"algorithm": "CLASSICAL", "selector": "PMIS", '
        '"interpolator": "D1", "smoother": {"scope": "j", '
        '"solver": "BLOCK_JACOBI", "relaxation_factor": 0.8, '
        '"monitor_residual": 0}, "max_iters": 1, "max_levels": 16, '
        '"min_coarse_rows": 64, "coarse_solver": "DENSE_LU_SOLVER", '
        '"monitor_residual": 0, "setup_location": "%s"}}}'
    )
    repeat = "--repeat" in sys.argv
    for loc in ("DEVICE", "HOST"):
        cfg = AMGConfig.from_string(cfg_s % loc)
        s = create_solver(cfg, "default")
        t0 = time.perf_counter()
        s.setup(A)
        setup_s = time.perf_counter() - t0
        # capture the COLD run's profile/levels before anything else
        prof = dict(getattr(s.precond, "setup_profile", {})) if hasattr(
            s, "precond") else {}
        levels = len(s.precond.levels) if hasattr(s, "precond") else None
        setup2_s = None
        if repeat:
            # second setup in the same process: XLA program cache is
            # warm, isolating the compile share of the first setup.
            # Free the first hierarchy first — holding two at large
            # sizes doubles peak RSS (observed OOM at 192^3 DEVICE).
            del s
            import gc

            gc.collect()
            s = create_solver(cfg, "default")
            t0 = time.perf_counter()
            s.setup(A)
            setup2_s = time.perf_counter() - t0
            warm_levels = (
                len(s.precond.levels) if hasattr(s, "precond") else None
            )
            if warm_levels != levels:
                # a cold/warm structure mismatch is a signal to report,
                # not a reason to discard hours of measurement
                prof["warm_levels_mismatch"] = warm_levels
        rec = {
            "n_side": n_side,
            "rows": A.n_rows,
            "setup_location": loc,
            "setup_s": round(setup_s, 2),
            "levels": levels,
        }
        if setup2_s is not None:
            rec["setup_warm_s"] = round(setup2_s, 2)
        if "warm_levels_mismatch" in prof:
            rec["warm_levels_mismatch"] = prof.pop(
                "warm_levels_mismatch")
        if prof:
            # the AMG scope's self-time totals (core/profiling.py)
            hs, ds = prof.get("host_s", 0.0), prof.get("device_s", 0.0)
            rec.update(
                {k: round(prof.get(k, 0.0), 2)
                 for k in ("host_s", "device_s", "transfer_s",
                           "compile_s", "unattributed_s")},
                host_share=round(hs / max(hs + ds, 1e-9), 3),
                scalar_syncs=prof.get("syncs"),
            )
        if do_solve:
            res = s.solve(b)
            rec["iterations"] = int(res.iters)
        print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
