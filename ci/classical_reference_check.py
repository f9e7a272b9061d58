"""The classical benchmark cell's hierarchy against the plain reference.

    python ci/classical_reference_check.py [--n 128] [--seed 1] \\
        [--locations DEVICE HOST]

Sets up the solver of ``benchmark/configs/poisson7_classical.json`` on its
operator (float32, 128^3 unless ``--n`` cuts it) once per
``setup_location``, in the order given, and prints one JSON line each: the
set-up's wall and compile seconds and the AMG set-up's own split (its
setup profile), the level sizes and formats, and the iterations, seconds
and float64 residual of one solve of the benchmark's first right-hand side
of ``--seed``.

For the first location it also downloads the levels and prints one JSON
line: for each level, the largest entry of ``Ac - R A P`` over the largest
of ``R A P``, with ``R A P`` computed by scipy in float64 from the
downloaded float32 ``A``, ``P`` and ``R``; and one V-cycle of the program
on the device against the float64 V-cycle of tests/classical_reference.py
over the same levels, as ``||x - x_ref|| / ||x_ref||``.
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
sys.path[:0] = [ROOT, os.path.join(ROOT, "tests")]


def _levels(solver):
    """The program's levels as float64 scipy CSR: ``[{"A", "P", "R"}]``."""
    out = []
    for lvl in solver.precond.levels:
        d = {"A": lvl.A.to_scipy().astype(np.float64).tocsr()}
        if lvl.P is not None:
            d["P"] = lvl.P.to_scipy().astype(np.float64).tocsr()
            d["R"] = lvl.R.to_scipy().astype(np.float64).tocsr()
        out.append(d)
    return out


def reference_check(solver, b, omega):
    import jax
    import jax.numpy as jnp

    import classical_reference as ref

    levels = _levels(solver)
    galerkin = []
    for k in range(len(levels) - 1):
        rap = (levels[k]["R"] @ levels[k]["A"] @ levels[k]["P"]).tocsr()
        diff = abs(levels[k + 1]["A"] - rap).max()
        galerkin.append(float(diff / abs(rap).max()))
    amg = solver.precond
    dtype = amg.levels[0].A.values.dtype
    bd = jnp.asarray(b, dtype)
    x = jax.jit(amg.make_step())(amg._collect_params(), bd,
                                 jnp.zeros_like(bd))
    got = np.asarray(x, np.float64)
    want = ref.v_cycle(levels, np.asarray(b, np.float64), omega)
    return {"galerkin_rel": galerkin,
            "v_cycle_rel": float(np.linalg.norm(got - want)
                                 / np.linalg.norm(want))}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--n", type=int, default=None)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--locations", nargs="+", default=["DEVICE", "HOST"])
    args = ap.parse_args(argv)

    from benchmark import generate, run
    from benchmark.entries.amg_solver import _format

    config = run.load_json(os.path.join(
        run.BENCH, "configs", "poisson7_classical.json"))
    if args.n:
        config.update(nx=args.n, ny=args.n, nz=args.n)
    traffic = run.load_json(os.path.join(run.BENCH, "traffic",
                                         "solve.json"))
    run._place_compile_cache(ROOT)
    import jax
    import jax.numpy as jnp

    import amgx_tpu
    from amgx_tpu.config.amg_config import AMGConfig
    from amgx_tpu.core.matrix import SparseMatrix
    from amgx_tpu.solvers import create_solver

    amgx_tpu.initialize()
    clock = run.CompileClock()
    A = generate.operator(config, np.float32)
    A64 = generate.operator(config, np.float64)
    b = generate.rhs(A.shape[0], traffic, args.seed, 0, np.float32)
    amg_cfg = config["amgx_config"]["solver"]["preconditioner"]
    omega = float(amg_cfg["smoother"]["relaxation_factor"])
    for i, loc in enumerate(args.locations):
        cfg = copy.deepcopy(config["amgx_config"])
        cfg["solver"]["preconditioner"]["setup_location"] = loc
        solver = create_solver(AMGConfig.from_string(json.dumps(cfg)),
                               "default")

        def setup():
            solver.setup(SparseMatrix.from_csr(
                A.indptr, A.indices, A.data, n_cols=A.shape[1]))

        setup_s, setup_compile_s = clock.timed(setup)
        prof = solver.precond.setup_profile
        t0 = time.perf_counter()
        res = solver.solve(jnp.asarray(b))
        x = np.asarray(res.x)
        solve_s = time.perf_counter() - t0
        rel = float(np.linalg.norm(b - A64 @ x.astype(np.float64))
                    / np.linalg.norm(b))
        line = {
            "rows": int(A.shape[0]), "setup_location": loc,
            "device": jax.devices()[0].device_kind,
            "setup_s": setup_s, "setup_compile_s": setup_compile_s,
            "amg_split_s": {k: prof.get(k) for k in (
                "host_s", "device_s", "transfer_s", "compile_s",
                "unattributed_s")},
            "builders": sorted(set(solver.precond.level_builders)),
            "levels": [{"rows": int(lvl.A.n_rows), "format": _format(lvl.A)}
                       for lvl in solver.precond.levels],
            "iterations": int(res.iters), "converged": bool(res.converged),
            "first_solve_s": solve_s, "residual_f64": rel,
        }
        print(json.dumps(line), flush=True)
        if i == 0:
            print(json.dumps({"setup_location": loc,
                              **reference_check(solver, b, omega)}),
                  flush=True)
        del solver, res, x
    return 0


if __name__ == "__main__":
    sys.exit(main())
