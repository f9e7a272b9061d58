#!/usr/bin/env bash
# CI entry point (reference ci/test.sh runs amgx_tests_launcher).
# Runs the full suite on the 8-device virtual CPU mesh (including the
# slow 62-config acceptance sweep), the native C-ABI build + demos
# (round-5: a C-ABI regression fails CI), refreshes the acceptance
# table, then the bench smoke on whatever backend is available.
set -e
cd "$(dirname "$0")/.."
python -m pytest tests/ -q
python -m pytest tests/ -q -m slow

# ---- guardrails: fault-injection matrix ------------------------------
# One JSON line of pass/fail per injection site (CPU backend); a
# recovery-path regression fails CI here before the bench runs.
JAX_PLATFORMS=cpu python ci/fault_smoke.py

# ---- failure domains: chaos soak -------------------------------------
# One JSON line; non-zero exit when mixed traffic (batched tickets +
# lockstep checkpointed sessions + a mid-soak drain + a warm-booted
# successor) under a seeded randomized device-loss/hang/shed fault
# schedule violates an invariant: any unhandled exception, an admitted
# ticket that never settles typed-or-success, a group planned onto a
# tripped device without a half-open probe, a session resuming with
# more than checkpoint-cadence step loss, a leaked affinity load
# reservation, or unbalanced settlement accounting.
JAX_PLATFORMS=cpu python ci/chaos_soak.py --ops 16

# ---- serve pipeline: throughput + latency floors ---------------------
# One JSON line; non-zero exit when batched speedup drops below the 3x
# floor, the per-ticket p50/p99 latency fields are missing/incoherent,
# or the steady state exceeds one host sync per group (async-pipeline
# regression).
JAX_PLATFORMS=cpu python ci/serve_bench.py

# ---- fleet front-end: overload + drain floors ------------------------
# One JSON line; non-zero exit when 2x-sustainable load produces any
# unhandled exception, any untyped reject (every shed must be a typed
# AdmissionRejected/Overloaded with retry_after_s), an interactive-lane
# p99 over its ceiling (batch must be the lane that degrades), or a
# mid-load drain that loses an admitted ticket / exports nothing.
JAX_PLATFORMS=cpu python ci/load_bench.py

# ---- multi-process fleet: wire + restart + breaker floors ------------
# One JSON line; non-zero exit when real worker subprocesses driven
# over the wire miss the scaling floor (2-worker >= 1.5x one worker on
# a >= 2-core host; no-collapse sanity floor on starved single-core
# CI), repeat fingerprints miss the cross-process affinity floor, any
# shed crosses the wire untyped or without retry_after_s, a mid-load
# rolling restart loses a ticket / pays a setup on the warm-booted
# replacement, or a kill -9 fails to requeue-or-type every in-flight
# ticket, trip the worker breaker, and half-open-close it on the
# replacement.
JAX_PLATFORMS=cpu python ci/fleet_bench.py

# ---- setup-artifact store: restore + warm-boot floors ----------------
# One JSON line; non-zero exit when load_setup restore drops below 3x
# over cold setup on the Poisson suite, or a warm-booted service fails
# to serve its first group for a persisted fingerprint as a hierarchy
# cache hit (store regression).
JAX_PLATFORMS=cpu python ci/store_bench.py

# ---- cheap preconditioner: precision + inexact-coarse gates ----------
# One JSON line; non-zero exit when the f64-refined mixed-precision or
# INEXACT-coarse configs need more than +10% retired inner-step
# equivalents over the f64/DenseLU baseline at unchanged final
# tolerance, when coarse_solver=INEXACT fails the measured
# setup:coarse_factor (2x) or store-bytes (3x) reduction floors on the
# large-coarse-level problem, or when a tripped
# refine_iteration_guard does not produce exactly one counted,
# converging f64 fallback.
JAX_PLATFORMS=cpu python ci/precision_bench.py

# ---- communication-free inner loops: parity + reduction gates --------
# One JSON line; non-zero exit when OPT_POLYNOMIAL or SSTEP_PCG needs
# more than +10% iterations (inner-CG-step equivalents, +s-1 s-step
# quantization allowance) over the PCG+AMG(Jacobi) baseline on the
# bench matrix, or when SSTEP_PCG traces to more than 2 global
# reductions per s steps (monitored PCG: 3 per step).
JAX_PLATFORMS=cpu python ci/smoother_bench.py

# ---- streaming solve sessions: steps/s + pipelining floors -----------
# One JSON line; non-zero exit when the session subsystem drops below
# 1.5x steps/s over the naive per-step one-shot resubmit baseline on
# the B=8 32^2 implicit-Euler sequence (or below hand-rolled lockstep
# batching), when a measured window performs more than one host sync
# per flushed step-group, or when no resetup work overlapped an
# in-flight solve (pipelining regression).
JAX_PLATFORMS=cpu python ci/session_bench.py

# ---- mesh serving: sharded placement floors --------------------------
# One JSON line; non-zero exit when batch-axis sharding across the 8
# simulated CPU devices drops below 2x single-device solves/s at B=32
# on the 56^2 Poisson family (best of three time-diversified
# interleaved attempts), sharded results diverge from unsharded
# beyond 1e-12 (bitwise expected; the record reports it), the steady
# state exceeds one host sync per group, the shared-convergence-mask
# loop traces to more than one psum site per iteration (or the
# default local mode executes any collective), the affinity router
# misses a warm fingerprint on the repeated-fingerprint workload, or
# the default single-device policy is not bitwise identical to the
# explicit one (pre-placement dispatch regression).
JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8 ${XLA_FLAGS:-}" \
    python ci/mesh_bench.py

# ---- domain decomposition: halo-exchange + weak-scaling floors -------
# One JSON line; non-zero exit when the 4-shard row-sharded PCG+AMG
# solve of the 128^2 Poisson problem diverges from the 1-shard
# reference (rtol 1e-10) or breaks +10% iteration parity, the
# fine-level SpMV traces more than one halo exchange per apply, PCG /
# SSTEP_PCG exceed their psum-site budgets (5 / 3), coarse-grid
# sparsification fails to shrink per-cycle halo bytes within parity,
# or (on multi-core hosts, where simulated-device overlap is
# physically possible) sharded solves/s drops below 1.5x single-shard.
JAX_PLATFORMS=cpu XLA_FLAGS="--xla_force_host_platform_device_count=8 ${XLA_FLAGS:-}" \
    python ci/halo_bench.py

# ---- matrix-free stencils: compression + fused-leg floors ------------
# One JSON line; non-zero exit when the MATRIX_FREE SpMV on the 32^3
# 7-point Poisson operator fails the 1.3x marginal per-SpMV speedup
# floor over DIA (geomean of best 3 of 5 interleaved chained-timing
# attempts), when the trace-time operator-pass counter does not show
# exactly one fine-grid pass per fused V-cycle descent leg (unfused
# 3(L-1)+1 vs fused 2(L-1)+1), or when the matrix-free / fused solves
# are not bitwise identical to the DIA reference at equal iterations.
JAX_PLATFORMS=cpu python ci/matrix_free_bench.py

# ---- unified telemetry: exposition + tracing + overhead --------------
# One JSON line; non-zero exit when the Prometheus exposition fails to
# parse or exports fewer than 38 metric names across the serve /
# admission / store / cache / setup-phase / solver / session / mesh
# placement / distributed placement sources,
# when a sampled gateway request does not produce a connected
# submit->admission->pad->dispatch->device->fetch span chain in the
# Chrome trace JSON, when a sampled streaming-session step does not
# produce its session-labeled chain, or when armed telemetry
# (sample=0) costs more than 3% of serve throughput vs disarmed
# (noise-hardened: min of floor/pair statistics, time-diversified
# retries).
JAX_PLATFORMS=cpu python ci/telemetry_check.py

# ---- native C ABI (VERDICT r4 #9) -----------------------------------
# Build from source and run both demos on CPU; assert exit 0 and the
# expected iteration count from the reference README sample (1 iter).
make -C native clean all
export PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}"
export JAX_PLATFORMS=cpu
export XLA_FLAGS="--xla_force_host_platform_device_count=8 ${XLA_FLAGS:-}"
out=$(./native/amgx_capi_demo /root/reference/examples/matrix.mtx \
      /root/reference/src/configs/FGMRES_AGGREGATION.json)
echo "$out"
echo "$out" | grep -q "status=0 iterations=1" || {
    echo "C-ABI capi demo: unexpected status/iterations" >&2; exit 1; }
dout=$(./native/amgx_dist_demo) || {
    echo "C-ABI dist demo failed" >&2; exit 1; }
echo "$dout" | grep -q "distributed solve: status=0" || {
    echo "C-ABI dist demo: unexpected status" >&2; exit 1; }
unset JAX_PLATFORMS

python ci/acceptance.py
