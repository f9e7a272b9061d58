"""Serve-layer throughput: pipelined batched solves vs a sequential
Python loop.

Prints ONE JSON line (the BENCH_*.json contract):
{"metric": "serve_batched_speedup", "value": <x>, ...} — value is the
wall-clock throughput ratio of the batched service path over a
sequential Python loop dispatching the SAME compiled per-system solve
(the strongest honest baseline: one jitted program, params swapped per
call — no recompiles charged to the loop).

Run on the CPU backend (the tier the acceptance gate measures):

    JAX_PLATFORMS=cpu python ci/serve_bench.py [--out BENCH_serve.json]

Methodology: B pattern-sharing Jacobi-PCG Poisson systems per group;
each timed cycle submits a full group (dispatch is non-blocking — the
async pipeline, PR 3) and consumes the tickets through their single
shared per-group fetch.  ``waves`` cycles per rep, best cycle of
``reps`` reps reported (the same submit+consume unit the PR 2 record
measured, so the throughput numbers are directly comparable).
Warm-up excluded (setup + compile amortize across a service's
lifetime, which is the serving scenario).  Alongside throughput the
record carries the new latency observability: steady-state per-ticket
p50/p99 and the host/device overlap ratio
((host_busy + device_busy - wall) / min(host_busy, device_busy),
clamped to [0, 1] — 0 means fully serialized stages, 1 means the
shorter side completely hidden).
"""

import argparse
import json
import sys
import time

# runnable from any cwd: the repo root precedes ci/ on the path
sys.path.insert(0, __file__.rsplit("/", 2)[0])


def run(shape=(16, 16), batch=16, reps=3, waves=8, config=None):
    import jax
    import numpy as np

    from amgx_tpu.config.amg_config import AMGConfig
    from amgx_tpu.core.matrix import SparseMatrix
    from amgx_tpu.io.poisson import jittered_poisson_family
    from amgx_tpu.serve import DEFAULT_CONFIG, BatchedSolveService
    from amgx_tpu.solvers.registry import create_solver, make_nested

    if config is None:
        config = DEFAULT_CONFIG
    systems = jittered_poisson_family(shape, batch, seed=0)
    n = systems[0][0].shape[0]

    # ---- batched service path (pipelined stream) -------------------
    svc = BatchedSolveService(config=config, max_batch=batch)
    svc.solve_many(systems)  # warm-up: setup + compile + first fetch
    svc.metrics.reset_latency()  # steady-state latency window only
    t_batch = float("inf")
    wall_total = 0.0
    results = None
    for _ in range(reps):
        for _w in range(waves):
            t0 = time.perf_counter()
            # the full group dispatches at max_batch (non-blocking);
            # ticket.result() runs the one shared fetch
            tickets = [svc.submit(sp, b) for sp, b in systems]
            results = [t.result() for t in tickets]
            cycle = time.perf_counter() - t0
            wall_total += cycle
            t_batch = min(t_batch, cycle)

    m = svc.metrics.snapshot()
    host_busy = m.get("host_busy_s", 0.0)
    device_busy = m.get("device_busy_s", 0.0)
    # overlap over the whole steady window (all reps ran back to back)
    overlap = 0.0
    if host_busy > 0 and device_busy > 0:
        tot_wall = max(wall_total, max(host_busy, device_busy))
        overlap = (host_busy + device_busy - tot_wall) / min(
            host_busy, device_busy
        )
        overlap = max(0.0, min(1.0, overlap))

    # ---- sequential Python loop baseline ---------------------------
    # strongest honest loop: setup and compiles OUTSIDE the loop (one
    # solver, one jitted solve, one jitted values-only rebuild); the
    # loop pays what every coefficient-swapping caller pays per
    # system — upload the new values, rebuild params on device
    # (replace_coefficients), solve, read the solution back.  The
    # batched path pays the same stages inside ITS timed region.
    cfg = AMGConfig.from_string(config)
    solver = make_nested(create_solver(cfg, "default"))
    A0 = SparseMatrix.from_scipy(systems[0][0])
    solver.setup(A0)
    tmpl, params_of = solver.make_batch_params()
    solve_one = jax.jit(solver.make_solve())
    rebuild = jax.jit(params_of)
    vals = [
        np.asarray(sp.data, dtype=A0.values.dtype) for sp, _ in systems
    ]
    import jax.numpy as jnp

    x0 = jnp.zeros(n, dtype=A0.values.dtype)
    bs_host = [np.asarray(b, dtype=A0.values.dtype) for _, b in systems]
    r = solve_one(rebuild(tmpl, jnp.asarray(vals[0])), jnp.asarray(
        bs_host[0]), x0)
    r.x.block_until_ready()  # warm-up: compile both programs
    t_seq = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        seq = []
        for v, b in zip(vals, bs_host):
            p = rebuild(tmpl, jnp.asarray(v))
            r = solve_one(p, jnp.asarray(b), x0)
            np.asarray(r.x)  # the caller consumes each solution
            seq.append(r)
        t_seq = min(t_seq, time.perf_counter() - t0)

    # parity spot-check: the speedup must not come from solving less
    for r, sref, (sp, b) in zip(results, seq, systems):
        xa, xb = np.asarray(r.x), np.asarray(sref.x)
        err = np.linalg.norm(xa - xb) / max(np.linalg.norm(xb), 1e-300)
        assert err < 1e-8, f"batched/sequential diverged: {err}"

    dev = jax.devices()[0]
    return {
        "metric": "serve_batched_speedup",
        "value": round(t_seq / t_batch, 2),
        "unit": "x vs sequential python loop",
        # placement-policy aware (PR 10): AMGX_TPU_PLACEMENT selects
        # the policy the service runs under (default: single-device,
        # unchanged); the record names it so a mesh/affinity run is
        # distinguishable
        "placement": svc.placement.name,
        "device": f"{dev.platform}"
        f" ({getattr(dev, 'device_kind', '?')})",
        "problem": f"poisson5_{shape[0]}x{shape[1]}_B{batch}",
        "config": "PCG+BLOCK_JACOBI",
        "n": n,
        "batch": batch,
        "waves": waves,
        "t_batched_s": round(t_batch, 5),
        "t_sequential_s": round(t_seq, 5),
        "batched_solves_per_s": round(batch / t_batch, 1),
        "sequential_solves_per_s": round(batch / t_seq, 1),
        "ticket_p50_s": round(m["ticket_p50_s"], 6),
        "ticket_p99_s": round(m["ticket_p99_s"], 6),
        "overlap_ratio": round(overlap, 3),
        "host_syncs_per_group": round(
            m.get("host_syncs", 0) / max(m.get("batches", 1), 1), 3
        ),
        "bucket_hit_rate": round(m["bucket_hit_rate"], 3),
        "pad_waste_frac": round(m.get("pad_waste_frac", 0.0), 3),
        "compiles": m.get("compiles", 0),
        "setups": m.get("setups", 0),
    }


def comm_free_compare(shape=(32, 32), batch=16, reps=5):
    """Communication-free serve A/B at B=``batch``: the recommended
    config (SSTEP_PCG s=4 over AMG(OPT_POLYNOMIAL 1+1) —
    serve.COMM_AVOIDING_CONFIG) vs the PCG + AMG(BLOCK_JACOBI 2+2)
    baseline, at EQUAL smoother flops per V-cycle.  Both run the same
    jittered Poisson family through the batched service to the same
    tolerance; best of ``reps`` submit+consume cycles.

    Reported per config:
      * solves_per_s — the end-to-end serving outcome at B=batch.
      * per_iteration_ms — cycle time over the inner-CG-step
        equivalents the vmapped group loop actually retires (its
        member at max iterations; one s-step outer = s steps).  On a
        single chip this sits near PARITY: the s-step block flops
        (Gram + block direction updates, ~25% of an outer iteration)
        buy back the per-step dots/norm/convergence dispatches.  On a
        sharded mesh each of those dots is a psum sync — the traced
        reductions_per_s_steps (2 vs 3s) is the term that turns into
        wall time there (doc/PERFORMANCE.md).
      * reductions_per_s_steps — traced global-reduction sites per s
        inner steps (ops/blas.reduction_counter).
    """
    from amgx_tpu.config.amg_config import AMGConfig
    from amgx_tpu.io.poisson import jittered_poisson_family
    from amgx_tpu.serve import COMM_AVOIDING_CONFIG, BatchedSolveService
    from amgx_tpu.core.matrix import SparseMatrix
    from amgx_tpu.solvers.registry import create_solver, make_nested

    baseline = (
        '{"config_version": 2, "solver": {"scope": "main",'
        ' "solver": "PCG", "max_iters": 200, "tolerance": 1e-8,'
        ' "monitor_residual": 1, "convergence": "RELATIVE_INI",'
        ' "preconditioner": {"scope": "amg", "solver": "AMG",'
        ' "algorithm": "AGGREGATION", "selector": "SIZE_8",'
        ' "smoother": {"scope": "sm", "solver": "BLOCK_JACOBI",'
        ' "relaxation_factor": 0.8, "max_iters": 2,'
        ' "monitor_residual": 0},'
        ' "presweeps": 1, "postsweeps": 1, "max_iters": 1,'
        ' "min_coarse_rows": 32, "max_levels": 10,'
        ' "structure_reuse_levels": -1,'
        ' "coarse_solver": "DENSE_LU_SOLVER", "cycle": "V",'
        ' "monitor_residual": 0}}}'
    )
    systems = jittered_poisson_family(shape, batch, seed=0)
    out = {}
    for name, config in (("baseline", baseline),
                         ("recommended", COMM_AVOIDING_CONFIG)):
        solver = make_nested(create_solver(
            AMGConfig.from_string(config), "default"
        ))
        scale = int(solver.iterations_scale)
        solver.setup(SparseMatrix.from_scipy(systems[0][0]))
        red = solver.reductions_per_iteration()
        svc = BatchedSolveService(config=config, max_batch=batch)
        svc.solve_many(systems)  # warm-up: setup + compile
        t_best, results = float("inf"), None
        for _ in range(reps):
            t0 = time.perf_counter()
            tickets = [svc.submit(sp, b) for sp, b in systems]
            results = [t.result() for t in tickets]
            t_best = min(t_best, time.perf_counter() - t0)
        m = svc.metrics.snapshot()
        assert m.get("fallback_solves", 0) == 0, (
            f"comm_free[{name}]: group fell off the batched path"
        )
        assert all(int(r.status) == 0 for r in results)
        # the vmapped group loop retires max-in-group iterations
        retired = max(int(r.iters) for r in results) * scale
        out[name] = {
            "t_cycle_s": round(t_best, 5),
            "inner_iterations": sum(
                int(r.iters) * scale for r in results
            ),
            "per_iteration_ms": round(t_best / retired * 1e3, 3),
            "solves_per_s": round(batch / t_best, 1),
            "_scale": scale,
            "_red": red,
        }
    # reductions per s steps, s = the recommended config's block size
    # (one s-step outer iteration IS s steps; per-step solvers
    # multiply their per-iteration count up to the same unit)
    s_rec = out["recommended"].pop("_scale")
    out["baseline"].pop("_scale")
    for name in ("baseline", "recommended"):
        red = out[name].pop("_red")
        if red is None:
            out[name]["reductions_per_s_steps"] = None
        else:
            out[name]["reductions_per_s_steps"] = (
                red if name == "recommended" else red * s_rec
            )
    out["throughput_speedup"] = round(
        out["recommended"]["solves_per_s"]
        / out["baseline"]["solves_per_s"],
        3,
    )
    out["per_iteration_speedup"] = round(
        out["baseline"]["per_iteration_ms"]
        / out["recommended"]["per_iteration_ms"],
        3,
    )
    out["configs"] = {
        "baseline": "PCG+AMG(BLOCK_JACOBI 2+2)",
        "recommended": "SSTEP_PCG(s=4)+AMG(OPT_POLYNOMIAL 1+1)",
    }
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None,
                    help="also write the JSON record to this file")
    ap.add_argument("--batch", type=int, default=16)
    ap.add_argument("--side", type=int, default=16,
                    help="2D Poisson side length")
    ap.add_argument("--waves", type=int, default=8,
                    help="groups per timed stream")
    args = ap.parse_args(argv)

    import amgx_tpu

    amgx_tpu.initialize()
    import jax

    if jax.default_backend() == "cpu":
        # f64 end-to-end on CPU (the tier-1 configuration): the
        # batched-vs-sequential parity check is exact there
        jax.config.update("jax_enable_x64", True)
    rec = run(shape=(args.side, args.side), batch=args.batch,
              waves=args.waves)
    # A/B at 32x32 (own default): large enough that SpMV flops, not
    # block-op dispatch, dominate an iteration — the serving regime
    # the recommended config targets
    rec["comm_free"] = comm_free_compare(batch=args.batch)
    line = json.dumps(rec)
    print(line)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    ok = True
    if rec["value"] < 3.0:
        print(
            f"serve_bench: speedup {rec['value']}x below the 3x "
            "acceptance floor",
            file=sys.stderr,
        )
        ok = False
    if not (0 < rec["ticket_p50_s"] <= rec["ticket_p99_s"]):
        print(
            "serve_bench: latency percentiles missing/incoherent: "
            f"p50={rec['ticket_p50_s']} p99={rec['ticket_p99_s']}",
            file=sys.stderr,
        )
        ok = False
    if rec["host_syncs_per_group"] > 1.0:
        print(
            "serve_bench: steady state exceeded one host sync per "
            f"group ({rec['host_syncs_per_group']})",
            file=sys.stderr,
        )
        ok = False
    cf = rec["comm_free"]
    if cf["throughput_speedup"] < 1.0:
        print(
            "serve_bench: recommended comm-avoiding config "
            "(SSTEP_PCG+opt-poly) lost the solves/s A/B vs "
            f"PCG+Jacobi at B={args.batch}: {cf}",
            file=sys.stderr,
        )
        ok = False
    if cf["per_iteration_speedup"] < 0.85:
        # single-chip guard band: per-iteration time must stay near
        # parity (the block-flop overhead bounded by what the saved
        # reductions buy back); the communication win itself is gated
        # as traced reduction counts
        print(
            "serve_bench: comm-avoiding per-iteration time regressed "
            f"past the 0.85 parity band: {cf}",
            file=sys.stderr,
        )
        ok = False
    red_rec = cf["recommended"]["reductions_per_s_steps"]
    if red_rec is None or red_rec > 2:
        print(
            "serve_bench: recommended config traces to more than 2 "
            f"reductions per s steps (or tracing failed): {cf}",
            file=sys.stderr,
        )
        ok = False
    return 0 if ok else 1


if __name__ == "__main__":
    raise SystemExit(main())
