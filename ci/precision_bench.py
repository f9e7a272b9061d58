"""Cheap-preconditioner CI gate: mixed-precision hierarchies + inexact
coarse solves under the f64 accuracy envelope (PR 13).

Prints ONE JSON line (same contract as the other ci/ gates) and exits
non-zero when:

* **retired-iteration parity** — on the parity problem, the
  f32-hierarchy, INEXACT-coarse, and combined configs need more than
  +10% retired iterations (inner-step equivalents) over the
  f64/DenseLU baseline, or any config misses the UNCHANGED final
  tolerance; the refinement-wrapped ``CHEAP_PRECONDITIONER_CONFIG``
  additionally gets an (inner_budget - 1) quantization allowance (an
  outer correction commits inner_budget steps at a time — the s-step
  allowance logic of ci/smoother_bench.py);
* **coarse-setup-time reduction** — on the coarse-cost problem (depth
  capped so the coarsest level stays large, the regime where DenseLU's
  O(n^3) bites), ``coarse_solver=INEXACT`` fails to cut the
  ``setup:coarse_factor`` phase by the floor factor;
* **store-bytes reduction** — the persisted INEXACT setup artifact
  (no dense factors) fails to be smaller than the DenseLU one by the
  floor factor;
* **fallback guardrail** — a tripped ``refine_iteration_guard`` does
  not produce exactly one counted f64 fallback that converges to the
  final tolerance.

Run on the CPU backend (the tier the acceptance gate measures):

    JAX_PLATFORMS=cpu python ci/precision_bench.py [--out FILE]
"""

import argparse
import json
import math
import os
import sys
import tempfile

# runnable from any cwd: the repo root precedes ci/ on the path
sys.path.insert(0, __file__.rsplit("/", 2)[0])

TOL = 1e-8
INNER_BUDGET = 8  # CHEAP_PRECONDITIONER_CONFIG inner PCG max_iters

COARSE_TIME_FLOOR = 2.0
STORE_BYTES_FLOOR = 3.0


def _parity_cfg(coarse, extra_amg=""):
    """Parity-problem config: both coarse solvers stop at the SAME
    coarse size (dense_lu_num_rows == min_coarse_rows), so the coarse
    SOLVE quality — not the hierarchy shape — is what the iteration
    gate compares."""
    return (
        '{"config_version": 2, "solver": {"scope": "main",'
        ' "solver": "PCG", "max_iters": 300,'
        f' "tolerance": {TOL}, "monitor_residual": 1,'
        ' "convergence": "RELATIVE_INI",'
        ' "preconditioner": {"scope": "amg", "solver": "AMG",'
        ' "algorithm": "AGGREGATION", "selector": "SIZE_8",'
        + extra_amg +
        ' "smoother": {"scope": "sm", "solver": "OPT_POLYNOMIAL",'
        ' "chebyshev_polynomial_order": 2, "monitor_residual": 0},'
        ' "presweeps": 1, "postsweeps": 1, "max_iters": 1,'
        ' "min_coarse_rows": 32, "dense_lu_num_rows": 32,'
        ' "max_levels": 10, "structure_reuse_levels": -1,'
        f' "coarse_solver": "{coarse}", "cycle": "V",'
        ' "monitor_residual": 0}}}'
    )


_MIXED = '"hierarchy_dtype": "FLOAT32", "level_dtype_policy": "ALL",'


def _coarse_cost_cfg(coarse):
    """Coarse-cost config: classical AMG with max_levels=2, so the
    coarsest operator stays large and the DenseLU factorization is the
    dominant coarse-setup cost (the mesh-serialization-point regime)."""
    return (
        '{"config_version": 2, "solver": {"scope": "main",'
        ' "solver": "PCG", "max_iters": 300,'
        f' "tolerance": {TOL}, "monitor_residual": 1,'
        ' "convergence": "RELATIVE_INI",'
        ' "preconditioner": {"scope": "amg", "solver": "AMG",'
        ' "smoother": {"scope": "sm", "solver": "OPT_POLYNOMIAL",'
        ' "chebyshev_polynomial_order": 2, "monitor_residual": 0},'
        ' "presweeps": 1, "postsweeps": 1, "max_iters": 1,'
        ' "max_levels": 2, "structure_reuse_levels": -1,'
        f' "coarse_solver": "{coarse}", "cycle": "V",'
        ' "monitor_residual": 0}}}'
    )


def _build(cfg_text, A):
    from amgx_tpu.config.amg_config import AMGConfig
    from amgx_tpu.solvers.registry import create_solver, make_nested

    s = make_nested(
        create_solver(AMGConfig.from_string(cfg_text), "default")
    )
    s.setup(A)
    return s


def _rel_residual(sp, b, res):
    import numpy as np

    x = np.asarray(res.x)
    return float(
        np.linalg.norm(b - sp @ x) / max(np.linalg.norm(b), 1e-300)
    )


def run():
    import numpy as np

    from amgx_tpu.core.matrix import SparseMatrix
    from amgx_tpu.io.poisson import poisson_scipy
    from amgx_tpu.serve import CHEAP_PRECONDITIONER_CONFIG

    problems = []
    rng = np.random.default_rng(0)

    # ---- (a) retired-iteration parity at unchanged tolerance --------
    side = 48
    sp = poisson_scipy((side, side)).tocsr()
    sp.sort_indices()
    b = rng.standard_normal(sp.shape[0])
    A = SparseMatrix.from_scipy(sp)

    parity = {}
    amg = None
    for name, cfg_text in (
        ("baseline", _parity_cfg("DENSE_LU_SOLVER")),
        ("mixed_f32", _parity_cfg("DENSE_LU_SOLVER", _MIXED)),
        ("inexact", _parity_cfg("INEXACT")),
        ("mixed_inexact", _parity_cfg("INEXACT", _MIXED)),
    ):
        s = _build(cfg_text, A)
        r = s.solve(b)
        rel = _rel_residual(sp, b, r)
        parity[name] = {
            "iters": int(r.iters),
            "rel_residual": rel,
        }
        if int(r.status) != 0:
            problems.append(f"{name}: status {int(r.status)}")
        if rel > 2 * TOL:
            problems.append(
                f"{name}: final tolerance degraded "
                f"(rel {rel:.2e} > {2 * TOL:.0e})"
            )

    cheap = _build(CHEAP_PRECONDITIONER_CONFIG, A)
    r = cheap.solve(b)
    rel = _rel_residual(sp, b, r)
    parity["cheap_refined"] = {
        "outer_iters": int(r.iters),
        "iters": int(cheap.last_inner_iters),
        "rel_residual": rel,
    }
    if int(r.status) != 0:
        problems.append(f"cheap_refined: status {int(r.status)}")
    if rel > 2 * TOL:
        problems.append(
            f"cheap_refined: final tolerance degraded (rel {rel:.2e})"
        )
    if cheap.precision_fallbacks:
        problems.append(
            "cheap_refined: precision fallback tripped on the healthy "
            "parity problem"
        )

    base_iters = parity["baseline"]["iters"]
    for name in ("mixed_f32", "inexact", "mixed_inexact",
                 "cheap_refined"):
        allow = (INNER_BUDGET - 1) if name == "cheap_refined" else 0
        ceiling = math.ceil(1.1 * base_iters) + allow
        if parity[name]["iters"] > ceiling:
            problems.append(
                f"{name}: {parity[name]['iters']} retired inner-step "
                f"equivalents exceeds ceiling {ceiling} (baseline "
                f"{base_iters} +10% +{allow})"
            )

    # ---- (b) coarse-setup-time + store-bytes reduction --------------
    side2 = 96
    sp2 = poisson_scipy((side2, side2)).tocsr()
    sp2.sort_indices()
    b2 = rng.standard_normal(sp2.shape[0])
    A2 = SparseMatrix.from_scipy(sp2)

    coarse = {}
    for name in ("DENSE_LU_SOLVER", "INEXACT"):
        times = []
        s = None
        for _ in range(2):
            s = _build(_coarse_cost_cfg(name), A2)
            prof = s.collect_setup_profile()
            times.append(float(prof.get("coarse_factor", 0.0)))
        r = s.solve(b2)
        if int(r.status) != 0:
            problems.append(f"coarse-cost {name}: status {int(r.status)}")
        fd, path = tempfile.mkstemp(suffix=".npz")
        os.close(fd)
        try:
            s.save_setup(path)
            size = os.path.getsize(path)
        finally:
            os.unlink(path)
        coarse[name] = {
            "coarse_factor_s": min(times),
            "store_bytes": int(size),
            "coarse_rows": int(s.precond.levels[-1].n_rows),
            "iters": int(r.iters),
        }
    t_dense = coarse["DENSE_LU_SOLVER"]["coarse_factor_s"]
    t_inx = coarse["INEXACT"]["coarse_factor_s"]
    time_ratio = t_dense / max(t_inx, 1e-9)
    if time_ratio < COARSE_TIME_FLOOR:
        problems.append(
            f"coarse-setup-time reduction {time_ratio:.2f}x below the "
            f"{COARSE_TIME_FLOOR}x floor (DenseLU {t_dense:.3f}s vs "
            f"INEXACT {t_inx:.3f}s)"
        )
    bytes_ratio = (
        coarse["DENSE_LU_SOLVER"]["store_bytes"]
        / max(coarse["INEXACT"]["store_bytes"], 1)
    )
    if bytes_ratio < STORE_BYTES_FLOOR:
        problems.append(
            f"store-bytes reduction {bytes_ratio:.2f}x below the "
            f"{STORE_BYTES_FLOOR}x floor"
        )

    # ---- (c) fallback-to-f64 on the guardrail trip ------------------
    from amgx_tpu.config.amg_config import AMGConfig
    from amgx_tpu.solvers.registry import create_solver, make_nested

    gcfg = AMGConfig.from_string(CHEAP_PRECONDITIONER_CONFIG)
    gcfg.set("refine_iteration_guard", 1, "main")
    guarded = make_nested(create_solver(gcfg, "default"))
    guarded.setup(A)
    rg = guarded.solve(b)
    relg = _rel_residual(sp, b, rg)
    fallback = {
        "precision_fallbacks": int(guarded.precision_fallbacks),
        "status": int(rg.status),
        "rel_residual": relg,
    }
    if guarded.precision_fallbacks != 1:
        problems.append(
            f"guardrail: {guarded.precision_fallbacks} fallbacks "
            "(expected exactly 1 on refine_iteration_guard=1)"
        )
    if int(rg.status) != 0 or relg > 2 * TOL:
        problems.append(
            f"guardrail fallback did not recover (status "
            f"{int(rg.status)}, rel {relg:.2e})"
        )
    fb = guarded._fallback_solver
    if fb is not None:
        import numpy as np  # noqa: F811

        for lvl in fb.inner.precond.levels:
            if np.dtype(lvl.A.values.dtype) != np.float64:
                problems.append(
                    "guardrail fallback hierarchy is not full "
                    "precision"
                )
                break

    import jax

    dev = jax.devices()[0]
    return {
        "metric": "precision_coarse_setup_speedup",
        "value": round(time_ratio, 2),
        "unit": "DenseLU / INEXACT setup:coarse_factor seconds "
                "(coarse-cost problem)",
        "device": f"{dev.platform}"
        f" ({getattr(dev, 'device_kind', '?')})",
        "store_bytes_ratio": round(bytes_ratio, 2),
        "parity": parity,
        "coarse_cost": coarse,
        "fallback": fallback,
        "parity_gate": "+10% retired inner-step equivalents "
                       f"(+{INNER_BUDGET - 1} for the refinement "
                       "wrapper) at unchanged final tolerance",
        "floors": {
            "coarse_setup_time": COARSE_TIME_FLOOR,
            "store_bytes": STORE_BYTES_FLOOR,
        },
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
        print(f"precision_bench: {p}", file=sys.stderr)
    return 0 if not problems else 1


if __name__ == "__main__":
    raise SystemExit(main())
