"""The control of the check: the plain reference solver put in the
program's place, computed one precision below the configuration's.

    python3 -m benchmark.control --workload <name> --seeds 1 2 3 \\
        [--precision bfloat16] [--count N]

For each seed this makes the cell's operator and right-hand sides as a run
does, solves each right-hand side of the ring (or its first ``N``) with a plain Jacobi-preconditioned CG
(``jax.numpy``, diagonal storage, no kernels) in ``--precision`` on the
device, and judges the solutions by the run's own check.  A configuration
in float32 has bfloat16 as its control; the check has to find it wrong.
The benchmark's runs never call this: it sets the upper end of the
residual limit, and a test keeps it at a small size.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

from benchmark import generate, run

_CONTROL_MAX_ITERS = 2000


def diagonals(A):
    """``(offsets, values (nd, n))`` of a square CSR matrix."""
    rows = np.repeat(np.arange(A.shape[0]), np.diff(A.indptr))
    offs = A.indices.astype(np.int64) - rows
    offsets = np.unique(offs)
    vals = np.zeros((len(offsets), A.shape[0]), np.float64)
    vals[np.searchsorted(offsets, offs), rows] = A.data
    return tuple(int(o) for o in offsets), vals


def plain_pcg(offsets, vals, b, tol, max_iters, dtype):
    """Jacobi-preconditioned CG from x0 = 0 in ``dtype``; returns x."""
    import jax
    import jax.numpy as jnp

    n = b.shape[0]
    lo, hi = max(0, -min(offsets)), max(0, max(offsets))

    def spmv(d, x):
        xp = jnp.pad(x, (lo, hi))
        y = jnp.zeros_like(x)
        for k, off in enumerate(offsets):
            y = y + d[k] * jax.lax.dynamic_slice_in_dim(xp, off + lo, n)
        return y

    @jax.jit
    def solve(d, b):
        inv_diag = 1.0 / d[offsets.index(0)]
        bnorm = jnp.sqrt(jnp.vdot(b, b))
        x = jnp.zeros_like(b)
        r = b
        z = inv_diag * r
        p = z
        rz = jnp.vdot(r, z)

        def cond(c):
            it, _, r, _, _ = c
            return (it < max_iters) & (jnp.sqrt(jnp.vdot(r, r))
                                       >= tol * bnorm)

        def body(c):
            it, x, r, p, rz = c
            q = spmv(d, p)
            alpha = rz / jnp.vdot(p, q)
            x = x + alpha * p
            r = r - alpha * q
            z = inv_diag * r
            rz_new = jnp.vdot(r, z)
            p = z + (rz_new / rz) * p
            return it + 1, x, r, p, rz_new

        return jax.lax.while_loop(cond, body, (0, x, r, p, rz))[1]

    x = solve(jnp.asarray(vals, dtype), jnp.asarray(b, dtype))
    return np.asarray(x.astype(jnp.float32))


def control(workload: str, seed: int, precision: str, *,
            count: int | None = None,
            config_overrides: dict | None = None) -> dict:
    """The check's numbers for the control on one seed."""
    spec = run.load_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    cell = run.named(spec["workloads"], workload, "workload")
    config = run.load_json(
        os.path.join(run.BENCH, "configs", f"{cell['config']}.json"))
    config.update(config_overrides or {})
    traffic = run.load_json(
        os.path.join(run.BENCH, "traffic", f"{cell['traffic']}.json"))
    outer = config["amgx_config"]["solver"]
    A = generate.operator(config, np.dtype(config["precision"]))
    offsets, vals = diagonals(A)
    del A
    outcomes = []
    for k in range(int(count or traffic["rhs_count"])):
        b = generate.rhs(vals.shape[1], traffic, seed, k)
        x = plain_pcg(offsets, vals, b, float(outer["tolerance"]),
                      _CONTROL_MAX_ITERS, precision)
        outcomes.append((k, x, True))
    correct, failed, checks = run.check(config, traffic, seed, outcomes)
    return {"workload": workload, "seed": seed, "precision": precision,
            "correct": correct, "failed": failed, "checks": checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--precision", default="bfloat16")
    ap.add_argument("--count", type=int, default=None)
    args = ap.parse_args(argv)
    import jax

    if jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 2
    for seed in args.seeds:
        print(json.dumps(control(args.workload, seed, args.precision,
                                  count=args.count)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
