"""Run one benchmark cell once on the chips of this machine, and print its
result as one JSON line.

    python3 -m benchmark.run --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

Everything that belongs to a cell is found by name: the cell in
``BENCHMARK.json``, its configuration in ``benchmark/configs/<config>.json``
(operator, AmgX solver config, entry, limits of the check), the operator's
generator in ``benchmark/operators/<operator>.py``, its traffic mix in
``benchmark/traffic/<traffic>.json``, which names the loop that drives the
window (``benchmark/loops/<loop>.py``) and the right-hand sides
(``benchmark/rhs/<rhs>.py``), the program's entry in
``benchmark/entries/<entry>.py``, each metric's reader in
``benchmark/metrics/<metric>.py`` and the chip's peaks in
``benchmark/peaks.json``.

A run: make the operator and the ring of right-hand sides from the seed,
set the program up, warm up one solve, then let the mix's loop drive the
program for ``--seconds``.  Once the window has closed, every solve's x is
checked against the plain reference: ``||b - A x|| / ||b||`` in float64
with the float64 operator.
With ``--trace 0`` the line carries the cell's end-to-end metrics; with
``--trace 1`` the window runs under the profiler and the line carries the
per-layer metrics read from the trace.  Without a TPU, or with fewer chips
than the cell asks for, the run prints no result and exits with 2.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import shutil
import sys
import tempfile
import time
from dataclasses import dataclass

import numpy as np

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class NoAccelerator(RuntimeError):
    pass


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def named(items: list, name: str, what: str) -> dict:
    for item in items:
        if item["name"] == name:
            return item
    raise KeyError(f"no {what} named {name!r}")


def cell_metrics(spec: dict, cell: str, traced: bool) -> list:
    """The metrics a run of ``cell`` reports: its end-to-end metrics, or
    with the profiler on its per-layer metrics."""
    group = spec["per_layer" if traced else "end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, summed from
    its monitoring events (as ``chip_smoke.py`` counts them)."""

    _EVENTS = (
        "/jax/core/compile/jaxpr_trace_duration",
        "/jax/core/compile/jaxpr_to_mlir_module_duration",
        "/jax/core/compile/backend_compile_duration",
    )

    def __init__(self):
        import jax

        self.s = 0.0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_kw):
        if event in self._EVENTS:
            self.s += duration
        if event == self._EVENTS[-1]:
            self.compiles += 1

    def timed(self, fn):
        """(wall seconds, compile seconds) of ``fn()``."""
        c0 = self.s
        t0 = time.perf_counter()
        fn()
        return time.perf_counter() - t0, self.s - c0


@dataclass
class Record:
    """What a run saw, as the metric readers take it."""

    setup: dict
    solves: list  # {"seconds", "iterations", "converged"} per solve
    structure: dict  # the entry's describe()
    device_kind: str
    trace: object = None  # benchmark.trace.Trace of a traced run

    @property
    def peak(self) -> dict:
        """The chip's peaks; a chip missing from the table is an error."""
        table = load_json(os.path.join(BENCH, "peaks.json"))
        if self.device_kind not in table:
            raise KeyError(
                f"{self.device_kind!r} is not in benchmark/peaks.json")
        return table[self.device_kind]

    @staticmethod
    def format_spec(fmt):
        """``benchmark/formats/<fmt>.json``, or None where there is none."""
        path = os.path.join(BENCH, "formats", f"{fmt}.json")
        return load_json(path) if fmt and os.path.exists(path) else None


def _place_compile_cache(root: str):
    """JAX's persistent cache at ``$JAX_COMPILATION_CACHE_DIR``, else at
    ``<checkout>/.jax_cache``; every program is cached, however quick."""
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(root, ".jax_cache"))
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def _devices(chips: int, require_tpu: bool):
    import jax

    devices = jax.devices()
    if require_tpu and devices[0].platform != "tpu":
        raise NoAccelerator(f"needs a TPU, JAX found {devices[0].platform!r}")
    if len(devices) < chips:
        raise NoAccelerator(f"needs {chips} chips, JAX found {len(devices)}")
    return devices


def check(config, traffic, seed, outcomes) -> tuple:
    """The plain reference, after the window: each solve's residual in
    float64 against the float64 operator, and its convergence flag."""
    from benchmark import generate

    A = generate.operator(config, np.float64)
    limits = config["check"]
    residuals, failed = [], 0
    for k, x, converged in outcomes:
        b = generate.rhs(A.shape[0], traffic, seed, k, np.float64)
        rel = float(np.linalg.norm(b - A @ x.astype(np.float64))
                    / np.linalg.norm(b))
        if not np.isfinite(rel):  # JSON has no NaN or infinity
            rel = sys.float_info.max
        residuals.append(rel)
        failed += (not converged) or not rel <= limits["residual"]
    unconverged = sum(not c for _, _, c in outcomes)
    checks = {
        "residual": {"value": max(residuals), "limit": limits["residual"]},
        "unconverged": {"value": unconverged,
                        "limit": limits["unconverged"]},
    }
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    return correct, failed, checks


def run(workload: str, seed: int, seconds: float, traced: bool, *,
        require_tpu: bool = True, config_overrides: dict | None = None,
        keep_trace: str | None = None) -> dict:
    """One run of one cell; returns the result line as a dict.

    ``config_overrides`` and ``require_tpu=False`` are for the tests,
    which drive a run on the CPU at a small size; ``keep_trace`` copies
    the profiler's output there."""
    t_start = time.perf_counter()
    spec = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell = named(spec["workloads"], workload, "workload")
    config = load_json(os.path.join(BENCH, "configs", f"{cell['config']}.json"))
    config.update(config_overrides or {})
    traffic = load_json(os.path.join(BENCH, "traffic",
                                     f"{cell['traffic']}.json"))
    metrics = cell_metrics(spec, workload, traced)
    readers = {m["name"]: importlib.import_module(
        f"benchmark.metrics.{m['name']}") for m in metrics}
    entry = importlib.import_module(f"benchmark.entries.{config['entry']}")
    loop = importlib.import_module(f"benchmark.loops.{traffic['loop']}")

    _place_compile_cache(ROOT)
    import jax

    from benchmark import generate

    devices = _devices(int(cell["chips"]), require_tpu)
    used = devices[:int(cell["chips"])]
    clock = CompileClock()

    t0 = time.perf_counter()
    dtype = np.dtype(config["precision"])
    A = generate.operator(config, dtype)
    rhs = generate.rhs_ring(A.shape[0], traffic, seed, dtype)
    generate_s = time.perf_counter() - t0
    program = entry.Program(config, A, rhs, used)
    del A, rhs
    program_s, program_compile_s = clock.timed(program.setup)
    program.stage()
    warm_s, _ = clock.timed(lambda: program.block(program.solve(0)))
    setup = {
        "generate_s": generate_s, "program_s": program_s,
        "program_compile_s": program_compile_s, "warm_s": warm_s,
        "compile_s": clock.s, "total_s": time.perf_counter() - t_start,
    }

    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if traced else None
    if traced:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    compiles0 = clock.compiles
    try:
        with jax.profiler.TraceAnnotation("bench_window"):
            handles = loop.drive(program, seconds, traffic,
                                 jax.profiler.TraceAnnotation)
    finally:
        if traced:
            jax.profiler.stop_trace()
    if clock.compiles != compiles0:
        raise RuntimeError(
            f"{clock.compiles - compiles0} compiles inside the window")

    peak = 0
    for d in used:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    solves, outcomes = [], []
    for k, secs, h in handles:
        x, iters, converged = program.result(h)
        solves.append({"seconds": secs, "iterations": iters,
                       "converged": converged})
        outcomes.append((k, x, converged))
    structure = program.describe()
    program.close()
    print("solves: iterations " + json.dumps([s["iterations"] for s in solves])
          + " seconds " + json.dumps([round(s["seconds"], 6) for s in solves])
          + " levels " + json.dumps(structure.get("levels")),
          file=sys.stderr, flush=True)
    del handles, program

    record = Record(setup, solves, structure, used[0].device_kind)
    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    line = {}
    if traced:
        from benchmark import trace

        try:
            record.trace = trace.load(trace_dir)
            if keep_trace:
                shutil.copytree(trace_dir, keep_trace, dirs_exist_ok=True)
        finally:
            shutil.rmtree(trace_dir, ignore_errors=True)
        device["busy_s"] = record.trace.busy_s
        device["window_s"] = record.trace.window_ns / 1e9
        line["breakdown"] = record.trace.breakdown()

    values = {}
    for m in metrics:
        v = readers[m["name"]].read(record)
        if v is not None:
            values[m["name"]] = {"value": float(v), "unit": m["unit"]}
    correct, failed, checks = check(config, traffic, seed, outcomes)
    return {"correct": correct, "attempted": len(solves), "failed": failed,
            "metrics": values, "device": device, **line, "checks": checks}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        line = run(args.workload, args.seed, args.seconds, bool(args.trace))
    except NoAccelerator as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    for name, c in line["checks"].items():
        ok = "ok" if c["value"] <= c["limit"] else "FAILED"
        print(f"check {name} {c['value']!r} limit {c['limit']!r} {ok}",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
