"""Communication-free inner loops: iteration-parity + reduction gates.

Prints ONE JSON line (same contract as ci/serve_bench.py):
{"metric": "sstep_reductions_per_s_steps", "value": <n>, ...} — value
is the measured global reductions per s inner CG steps of the s-step
solver (the headline communication win: ~2 vs ~3s for classic
monitored PCG), alongside the per-config iteration table.

Run on the CPU backend (the tier the acceptance gate measures):

    JAX_PLATFORMS=cpu python ci/smoother_bench.py [--out BENCH.json]

Bench matrix: 2D Poisson variants (isotropic, jittered-coefficient,
anisotropic) solved by PCG/SSTEP_PCG over an aggregation AMG V-cycle.
Configs, at EQUAL smoother flops per cycle (Jacobi 2 pre + 2 post
sweeps ~ degree-2 polynomial 1 + 1):

  pcg_jacobi     PCG        + AMG(BLOCK_JACOBI 2+2)   <- baseline
  pcg_optpoly    PCG        + AMG(OPT_POLYNOMIAL 1+1)
  sstep_jacobi   SSTEP_PCG4 + AMG(BLOCK_JACOBI 2+2)
  sstep_optpoly  SSTEP_PCG4 + AMG(OPT_POLYNOMIAL 1+1) <- recommended

Gates (non-zero exit on violation):
  * iteration parity: every non-baseline config converges within +10%
    of the baseline's iteration count on every matrix entry, counted
    in inner-CG-step equivalents; s-step configs additionally get the
    s-1 quantization allowance (an s-step outer iteration commits s
    steps at a time, so counts round UP to multiples of s — overshoot,
    not lost convergence; doc/PERFORMANCE.md).
  * reductions: SSTEP_PCG traces to <= 2 reductions per outer
    iteration (= per s steps) — one fused Gram block + one monitor
    norm — while monitored PCG traces to 3 per step.
  * every config converges (status 0) on every matrix entry.
"""

import argparse
import json
import math
import sys

# runnable from any cwd: the repo root precedes ci/ on the path
sys.path.insert(0, __file__.rsplit("/", 2)[0])

S_STEP = 4

_CONFIGS = (
    ("pcg_jacobi", "PCG", "BLOCK_JACOBI", 2, 2, ""),
    ("pcg_optpoly", "PCG", "OPT_POLYNOMIAL", 1, 1, ""),
    ("sstep_jacobi", "SSTEP_PCG", "BLOCK_JACOBI", 2, 2,
     f'"s_step": {S_STEP},'),
    ("sstep_optpoly", "SSTEP_PCG", "OPT_POLYNOMIAL", 1, 1,
     f'"s_step": {S_STEP},'),
)


def _amg_cfg(outer, smoother, pre, post, extra_outer=""):
    return (
        '{"config_version": 2, "solver": {"scope": "main",'
        f' "solver": "{outer}", "max_iters": 400,'
        ' "tolerance": 1e-8, "monitor_residual": 1,'
        f' "convergence": "RELATIVE_INI", {extra_outer}'
        ' "preconditioner": {"scope": "amg", "solver": "AMG",'
        ' "algorithm": "AGGREGATION", "selector": "SIZE_8",'
        f' "smoother": {{"scope": "sm", "solver": "{smoother}",'
        ' "relaxation_factor": 0.8,'
        ' "chebyshev_polynomial_order": 2, "monitor_residual": 0},'
        f' "presweeps": {pre}, "postsweeps": {post}, "max_iters": 1,'
        ' "min_coarse_rows": 32, "max_levels": 10,'
        ' "structure_reuse_levels": -1,'
        ' "coarse_solver": "DENSE_LU_SOLVER", "cycle": "V",'
        ' "monitor_residual": 0}}}'
    )


def _matrix_entries():
    """(name, scipy_csr, rhs) bench entries."""
    import numpy as np
    import scipy.sparse as sps

    from amgx_tpu.io.poisson import poisson_scipy

    side = 24
    entries = []

    sp = poisson_scipy((side, side)).tocsr()
    sp.sort_indices()
    rng = np.random.default_rng(0)
    entries.append(("poisson", sp, rng.standard_normal(sp.shape[0])))

    # jittered coefficients: the pattern-sharing serve family member
    spj = sp.copy()
    spj.data = spj.data * (
        1.0 + 0.1 * rng.standard_normal(spj.data.shape)
    )
    # re-symmetrize (SPD for CG) and keep diagonal dominance
    spj = ((spj + spj.T) * 0.5).tocsr()
    spj = (spj + sps.diags_array(
        np.abs(spj).sum(axis=1).ravel()
        - np.abs(spj.diagonal()) - spj.diagonal() + 0.1
    )).tocsr()
    spj.sort_indices()
    entries.append(
        ("jittered", spj, rng.standard_normal(spj.shape[0]))
    )

    # anisotropic 5-point stencil (eps * d_xx + d_yy)
    eps = 0.1
    n1 = side
    ex = np.ones(n1)
    t = sps.diags_array(
        [-ex[:-1], 2 * ex, -ex[:-1]], offsets=[-1, 0, 1]
    )
    eye = sps.eye_array(n1)
    spa = (eps * sps.kron(t, eye) + sps.kron(eye, t)).tocsr()
    spa.sort_indices()
    entries.append(
        ("anisotropic", spa, rng.standard_normal(spa.shape[0]))
    )
    return entries


def run():
    from amgx_tpu.config.amg_config import AMGConfig
    from amgx_tpu.core.matrix import SparseMatrix
    from amgx_tpu.solvers.registry import create_solver, make_nested

    problems = []
    table = {}
    reductions = {}
    for cfg_name, outer, smoother, pre, post, extra in _CONFIGS:
        cfg = AMGConfig.from_string(
            _amg_cfg(outer, smoother, pre, post, extra)
        )
        per_entry = {}
        for ename, sp, b in _matrix_entries():
            s = make_nested(create_solver(cfg, "default"))
            s.setup(SparseMatrix.from_scipy(sp))
            res = s.solve(b)
            if int(res.status) != 0:
                problems.append(
                    f"{cfg_name}/{ename}: status {int(res.status)}"
                )
            # inner-CG-step equivalents (one s-step outer = s steps)
            per_entry[ename] = int(res.iters) * int(
                s.iterations_scale
            )
            if cfg_name not in reductions:
                red = s.reductions_per_iteration()
                reductions[cfg_name] = {
                    "per_outer_iteration": red,
                    "per_s_steps": red
                    if outer == "SSTEP_PCG"
                    else (red or 0) * S_STEP,
                }
        table[cfg_name] = per_entry

    # ---- gates ---------------------------------------------------------
    base = table["pcg_jacobi"]
    for cfg_name, outer, _sm, _p, _q, _x in _CONFIGS[1:]:
        # the s-step quantization allowance: outer iterations commit s
        # steps at a time, so inner-equivalent counts round up to
        # multiples of s (overshoot, not lost convergence)
        allow = (S_STEP - 1) if outer == "SSTEP_PCG" else 0
        for ename, iters in table[cfg_name].items():
            ceiling = math.ceil(1.1 * base[ename]) + allow
            if iters > ceiling:
                problems.append(
                    f"{cfg_name}/{ename}: {iters} inner iterations "
                    f"exceeds ceiling {ceiling} "
                    f"(baseline {base[ename]} +10% +{allow})"
                )

    for cfg_name in ("sstep_jacobi", "sstep_optpoly"):
        per_s = reductions[cfg_name]["per_s_steps"]
        if per_s is None or per_s > 2:
            problems.append(
                f"{cfg_name}: {per_s} reductions per {S_STEP} steps "
                "(floor: <= 2 — one fused Gram + one monitor norm)"
            )
    pcg_red = reductions["pcg_jacobi"]["per_outer_iteration"]
    if pcg_red != 3:
        problems.append(
            f"pcg_jacobi: {pcg_red} reductions/iteration "
            "(monitored PCG traces to 3: two dots + monitor norm)"
        )

    import jax

    dev = jax.devices()[0]
    sstep_red = reductions["sstep_optpoly"]["per_s_steps"]
    return {
        "metric": "sstep_reductions_per_s_steps",
        "value": sstep_red,
        "unit": f"global reductions per s={S_STEP} CG steps "
                "(PCG baseline: 3 per step)",
        "device": f"{dev.platform}"
        f" ({getattr(dev, 'device_kind', '?')})",
        "s_step": S_STEP,
        "iterations": table,
        "reductions": reductions,
        "baseline": "pcg_jacobi",
        "recommended": "sstep_optpoly",
        "parity_gate": "+10% inner iterations (+s-1 for s-step)",
        "ok": not problems,
    }, problems


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None,
                    help="also write the JSON record to this file")
    args = ap.parse_args(argv)

    import amgx_tpu

    amgx_tpu.initialize()
    import jax

    if jax.default_backend() == "cpu":
        # f64 end-to-end on CPU (the tier-1 configuration)
        jax.config.update("jax_enable_x64", True)
    rec, problems = run()
    line = json.dumps(rec)
    print(line)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    for p in problems:
        print(f"smoother_bench: {p}", file=sys.stderr)
    return 0 if not problems else 1


if __name__ == "__main__":
    raise SystemExit(main())
