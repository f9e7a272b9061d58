"""Tracing/profiling hooks (reference amgx_timer.h:32-60 nvtxRange +
levelProfile, profile.h phase markers; SURVEY §5.1).

TPU mapping: NVTX ranges become ``jax.profiler.TraceAnnotation`` (host
trace spans) for API-level calls and set-up phases, on the same clock
as the device trace, and ``jax.named_scope`` (HLO op metadata, visible
in xprof/tensorboard traces) for traced compute; the per-level
tic/toc map becomes :class:`LevelProfile`.
"""

from __future__ import annotations

import contextlib
import os
import threading
import time
from collections import defaultdict

import jax
import numpy as np


# ----------------------------------------------------------------------
# setup-phase profiling (the cold-setup observability surface)
#
# The AMG driver opens a setup_profile_scope around hierarchy
# construction; coarsening code (amg/classical.py, amg/aggregation.py,
# amg/device_setup.py) wraps its stages in setup_phase(...) without
# needing a handle to the solver.  Every phase is one span on the
# profiler's clock (a ``setup:<phase>`` TraceAnnotation) of one kind:
#
#   host      numpy/scipy work and the dispatch of device programs
#             whose results the set-up does not wait for;
#   device    from a device program's dispatch until its result is
#             ready, closed at the sync the set-up already makes;
#   transfer  host<->device copies, each to completion.
#
# Each open scope accumulates, on SELF time (a span's time less that
# of the spans opened inside it, so no second is counted twice), the
# totals host_s / device_s / transfer_s, JAX's compile time compile_s
# (taken out of the span open when the compile ran), and
# unattributed_s: time inside the scope but in no span.  Together
# they partition the scope's wall time.  Phase keys (``aggregation``,
# ``rap_execute``, ...) keep their inclusive meaning and go to the
# innermost scope only; the totals go to every enclosing scope, so a
# caller's scope around an upload and a solver's setup() sums the
# nested solvers' scopes too.  The span and scope stacks are
# thread-local so concurrent setups (serve compile worker +
# foreground) never write into each other's profiles.

_setup_tls = threading.local()

SPAN_KINDS = ("host", "device", "transfer")
SETUP_TOTALS = ("host_s", "device_s", "transfer_s", "compile_s",
                "unattributed_s")
_KIND_TOTAL = {"host": "host_s", "device": "device_s",
               "transfer": "transfer_s", None: "unattributed_s"}
# the events benchmark/run.py CompileClock sums
_COMPILE_EVENTS = frozenset((
    "/jax/core/compile/jaxpr_trace_duration",
    "/jax/core/compile/jaxpr_to_mlir_module_duration",
    "/jax/core/compile/backend_compile_duration",
))
_compile_listener = [False]

# module-level transfer/sync accumulators — test-countable the same way
# serve's _fetch_host/_block_ready hooks are (tests snapshot, run a
# setup, and assert on the delta).  [batches, arrays, bytes] / [syncs].
# Lock-guarded: concurrent setups (serve compile worker + foreground)
# must not lose increments to interleaved read-modify-writes.
setup_transfer_count = [0, 0, 0]
setup_sync_count = [0]
_counter_lock = threading.Lock()


class _Frame:
    """One open span (or scope, ``kind`` None) on this thread."""

    __slots__ = ("kind", "scopes", "t0", "child_s", "compile_s")

    def __init__(self, kind, scopes, t0):
        self.kind = kind
        self.scopes = scopes
        self.t0 = t0
        self.child_s = 0.0
        self.compile_s = 0.0


def _setup_stack():
    st = getattr(_setup_tls, "stack", None)
    if st is None:
        st = _setup_tls.stack = []
        _setup_tls.frames = []
        _setup_tls.compiles = []
        _setup_tls.level = None
    return st


def _on_compile_event(event, duration, **_kw):
    """Monitoring listener: charge a compile to the innermost frame of
    the compiling thread.  Events nest (a jit traced inside another's
    trace reports its own duration), so a later event that encloses
    earlier ones replaces them and each compile second counts once."""
    if event not in _COMPILE_EVENTS:
        return
    frames = getattr(_setup_tls, "frames", None)
    if not frames:
        return
    start = time.perf_counter() - duration
    done = _setup_tls.compiles
    inner = 0.0
    while done and done[-1][0] >= start:
        inner += done.pop()[1]
    done.append((start, duration))
    frames[-1].compile_s += duration - inner


def _watch_compiles():
    if not _compile_listener[0]:
        with _counter_lock:
            if not _compile_listener[0]:
                jax.monitoring.register_event_duration_secs_listener(
                    _on_compile_event)
                _compile_listener[0] = True


def _open_frame(kind):
    scopes = _setup_tls.stack
    if len(scopes) > 1:  # one dict entered twice accumulates once
        scopes = list({id(p): p for p in scopes}.values())
    frame = _Frame(kind, tuple(scopes), time.perf_counter())
    _setup_tls.frames.append(frame)
    return frame


def _close_frame(frame) -> float:
    """Pop ``frame``, charge its self time and compile time to its
    scopes, and return its wall time."""
    dur = time.perf_counter() - frame.t0
    frames = _setup_tls.frames
    frames.pop()
    if frames:
        frames[-1].child_s += dur
    else:
        _setup_tls.compiles.clear()
    key = _KIND_TOTAL[frame.kind]
    self_s = dur - frame.child_s - frame.compile_s
    for p in frame.scopes:
        p[key] = p.get(key, 0.0) + self_s
        p["compile_s"] = p.get("compile_s", 0.0) + frame.compile_s
    return dur


@contextlib.contextmanager
def setup_profile_scope(profile: dict):
    """Activate ``profile`` as this thread's setup-phase sink.  Phase
    keys go to the innermost scope (a smoother's own AMG setup
    profiles its phases into its own dict, not its parent's); the
    five ``SETUP_TOTALS`` go to every open scope, so they cover
    everything inside, nested scopes included, each second once."""
    _watch_compiles()
    st = _setup_stack()
    st.append(profile)
    frame = _open_frame(None)
    try:
        yield profile
    finally:
        _close_frame(frame)
        st.pop()
        for k in SETUP_TOTALS:
            profile.setdefault(k, 0.0)


def active_setup_profile() -> dict | None:
    st = _setup_stack()
    return st[-1] if st else None


@contextlib.contextmanager
def setup_level(level_id: int):
    """Tag the spans opened inside with ``level=level_id``."""
    _setup_stack()
    outer = _setup_tls.level
    _setup_tls.level = int(level_id)
    try:
        yield
    finally:
        _setup_tls.level = outer


@contextlib.contextmanager
def setup_phase(name: str, kind: str = "host"):
    """One setup span of ``kind`` (host, device or transfer; see the
    comment above): a ``setup:<name>`` TraceAnnotation with ``kind``
    and, inside a level's build, ``level`` as metadata.  Inside a
    scope its wall time accumulates into the phase key ``name`` of the
    innermost scope and its self time into the scopes' totals.  When
    request tracing is on (``AMGX_TPU_TRACE_SAMPLE``), the interval
    also lands in the telemetry span buffer, on the same timeline as
    serve spans.  Outside a scope and with request tracing off it costs
    one TraceMe check and one clock pair."""
    st = _setup_stack()
    level = _setup_tls.level
    meta = {"kind": kind} if level is None else {"kind": kind,
                                                 "level": level}
    tracer = _span_recorder()
    with jax.profiler.TraceAnnotation(f"setup:{name}", **meta):
        if not st:
            t0 = time.perf_counter()
            try:
                yield
            finally:
                if tracer is not None:
                    tracer(f"setup:{name}", t0, time.perf_counter())
            return
        prof = st[-1]
        frame = _open_frame(kind)
        try:
            yield
        finally:
            dur = _close_frame(frame)
            prof[name] = prof.get(name, 0.0) + dur
            if tracer is not None:
                tracer(f"setup:{name}", frame.t0, frame.t0 + dur)


def _span_recorder():
    """Telemetry span hook: a ``record(name, t0, t1)`` callable when
    request tracing is sampled on, else None.  Lazy import — the
    telemetry package depends on nothing here, so the one-way edge
    stays acyclic."""
    from amgx_tpu.telemetry import tracing as _tracing

    if not _tracing.tracing_enabled():
        return None

    def rec(name, t0, t1):
        _tracing.record_span(name, t0, t1, _tracing.ambient())

    return rec


def count_setup_sync(n: int = 1):
    """Record ``n`` device->host synchronizations performed during
    setup (scalar readbacks of the device pipeline) in the module
    counter and in the active profile's ``syncs``."""
    with _counter_lock:
        setup_sync_count[0] += n
    prof = active_setup_profile()
    if prof is not None:
        prof["syncs"] = prof.get("syncs", 0) + n


def count_setup_transfer(n_arrays: int, n_bytes: int = 0):
    """Record one host->device transfer BATCH of ``n_arrays`` arrays.
    AMG set-up performs exactly one per hierarchy (the batched
    finalize); an eager ``from_csr`` upload counts one of its own."""
    with _counter_lock:
        setup_transfer_count[0] += 1
        setup_transfer_count[1] += int(n_arrays)
        setup_transfer_count[2] += int(n_bytes)
    prof = active_setup_profile()
    if prof is not None:
        prof["transfer_batches"] = prof.get("transfer_batches", 0) + 1
        prof["transfer_arrays"] = (
            prof.get("transfer_arrays", 0) + int(n_arrays)
        )


def setup_upload(tree, name: str = "upload"):
    """``jax.device_put`` of a pytree of host arrays as one ``transfer``
    span that lasts until the copies have landed."""
    with setup_phase(name, "transfer"):
        out = jax.device_put(tree)
        jax.block_until_ready(out)
    return out


def setup_download(tree, name: str = "download"):
    """``jax.device_get`` of a pytree of device arrays (numpy leaves
    pass through) as one ``transfer`` span; the read blocks until the
    copies are on the host."""
    with setup_phase(name, "transfer"):
        return jax.device_get(tree)


def setup_transfer(leaves):
    """Ship a list of array leaves host->device as ONE batched
    ``jax.device_put`` (the store-restore lever, store/serialize.py
    unflatten), counting it through the transfer hooks and timing it
    into the active profile's ``transfer`` phase.  Device-resident
    leaves pass through unchanged inside the same batch."""
    host = [l for l in leaves if isinstance(l, np.ndarray)]
    n_bytes = sum(l.nbytes for l in host)
    out = setup_upload(leaves, "transfer") if leaves else []
    count_setup_transfer(len(host), n_bytes)
    return out


def setup_profile_table(profile: dict) -> str:
    """Render a setup profile for the AMGX_TPU_SETUP_PROFILE=1 dump."""
    lines = ["    setup phase                     value"]
    for k in sorted(profile):
        v = profile[k]
        if isinstance(v, float):
            lines.append(f"    setup:{k:<24s} {v:>12.6f} s")
        else:
            lines.append(f"    setup:{k:<24s} {v!s:>12}")
    return "\n".join(lines)


def setup_profile_dump_enabled() -> bool:
    return os.environ.get("AMGX_TPU_SETUP_PROFILE") == "1"


class _TracedRange:
    """TraceAnnotation plus a telemetry span: the jax profiler sees
    the range as before, and the telemetry span buffer gets the same
    interval attributed to the thread's ambient trace context."""

    __slots__ = ("_name", "_ann", "_rec", "_t0")

    def __init__(self, name, ann, rec):
        self._name = name
        self._ann = ann
        self._rec = rec

    def __enter__(self):
        self._t0 = time.perf_counter()
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        self._rec(self._name, self._t0, time.perf_counter())
        return False


def trace_range(name: str):
    """Host-side trace span around an API call (NVTX-range analogue;
    reference amgx_c.cu:2747 nvtxRange per AMGX_* entry).  With
    request tracing sampled on, the same interval also lands in the
    telemetry span buffer (one timeline for API ranges, setup phases,
    and serve spans)."""
    ann = jax.profiler.TraceAnnotation(name)
    rec = _span_recorder()
    if rec is None:
        return ann
    return _TracedRange(name, ann, rec)


def named_scope(name: str):
    """Compile-time scope: tags the HLO ops emitted inside it so device
    traces attribute time per cycle phase (NVTX-on-device analogue)."""
    return jax.named_scope(name)


def percentile(samples, q: float) -> float | None:
    """Linear-interpolated percentile of a sequence (q in [0, 100]).
    Small-sample friendly: with one sample every percentile IS it.
    An EMPTY sequence returns None — never NaN or IndexError — so
    consumers that predict from percentiles (the gateway's shed
    predictor) can distinguish "no data yet" from "zero latency" and
    must treat None as *admit*, not as a zero-latency promise."""
    xs = sorted(samples)
    if not xs:
        return None
    if len(xs) == 1:
        return float(xs[0])
    pos = (len(xs) - 1) * (q / 100.0)
    lo = int(pos)
    hi = min(lo + 1, len(xs) - 1)
    frac = pos - lo
    return float(xs[lo] * (1.0 - frac) + xs[hi] * frac)


class LatencyReservoir:
    """Bounded ring of latency samples for tail-quantile reporting
    (p50/p99 of per-ticket serve latency).  A ring — not a sketch —
    because serve traffic is bursty and the QUESTION is always about
    recent behaviour; ``cap`` bounds memory regardless of uptime.
    Thread safety is the caller's job (ServeMetrics holds its lock
    around add/summary)."""

    def __init__(self, cap: int = 2048):
        self.cap = int(cap)
        self._samples: list = []
        self._next = 0
        self.count = 0  # lifetime samples, beyond the ring

    def add(self, seconds: float):
        s = float(seconds)
        if len(self._samples) < self.cap:
            self._samples.append(s)
        else:
            self._samples[self._next] = s
            self._next = (self._next + 1) % self.cap
        self.count += 1

    def clear(self):
        """Drop all samples (e.g. to exclude warm-up tickets from a
        steady-state quantile window)."""
        self._samples.clear()
        self._next = 0
        self.count = 0

    def percentile(self, q: float) -> float | None:
        """Percentile of the ring, or None when no sample has ever
        landed (empty-reservoir contract: "no data" is not "0 s")."""
        return percentile(self._samples, q)

    def summary(self) -> dict:
        xs = self._samples
        return {
            "count": self.count,
            "mean_s": sum(xs) / len(xs) if xs else 0.0,
            # summary keys stay float-valued (0.0 when empty) — the
            # snapshot/table exporters format them; the None contract
            # lives on percentile() where predictors read it
            "p50_s": percentile(xs, 50.0) or 0.0,
            "p99_s": percentile(xs, 99.0) or 0.0,
            "max_s": max(xs) if xs else 0.0,
        }


class LevelProfile:
    """Accumulating tic/toc phase map (reference amgx_timer.h:46-60).

    Thread-safe: serve mutates one shared instance from submit
    threads, the flusher, and the dispatch worker concurrently, and a
    telemetry snapshot may iterate it at any moment — a bare
    defaultdict there is exactly the "dictionary changed size during
    iteration" torn-read window the PR 7 audit closed.  Mutate via
    :meth:`phase`/:meth:`add`; read via :meth:`snapshot` (the
    ``times``/``counts`` attributes remain for single-threaded
    callers)."""

    def __init__(self):
        self.times = defaultdict(float)
        self.counts = defaultdict(int)
        self._lock = threading.Lock()

    def add(self, name: str, seconds: float, count: int = 1):
        """Locked accumulate — the API for cross-thread writers."""
        with self._lock:
            self.times[name] += float(seconds)
            self.counts[name] += count

    @contextlib.contextmanager
    def phase(self, name: str):
        t0 = time.perf_counter()
        try:
            yield
        finally:
            self.add(name, time.perf_counter() - t0)

    def snapshot(self) -> dict:
        """Consistent point-in-time copy: {"times": ..., "counts":
        ...} as plain dicts (safe to iterate/serialize)."""
        with self._lock:
            return {"times": dict(self.times), "counts": dict(self.counts)}

    def table(self) -> str:
        snap = self.snapshot()
        times, counts = snap["times"], snap["counts"]
        lines = ["    phase                          calls      total_s"]
        for k in sorted(times):
            lines.append(
                f"    {k:<30s} {counts[k]:>5d} {times[k]:>12.6f}"
            )
        return "\n".join(lines)
