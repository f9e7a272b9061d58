"""Reduction of one run's profiler trace to what the per-layer metrics
read: device busy time, each operation's self time with its scope path
and HLO category, and the host's own spans.

A TPU trace holds, per chip, a plane ``/device:TPU:<k>`` whose ``XLA Ops``
line has one event per executed HLO operation.  Events nest: a ``while``
loop's event spans the events of its body, so each event's self time is
its duration less that of the events directly inside it.  The host plane
``/host:CPU`` carries the harness's ``jax.profiler.TraceAnnotation``
spans (``bench_*``) on the same clock; the window is ``bench_window``.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

from benchmark import xspace

WINDOW_SPAN = "bench_window"
SPAN_PREFIX = "bench_"
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_OPS_LINE = "XLA Ops"
_COLLECTIVE = re.compile(
    r"all-reduce|all-gather|collective-permute|reduce-scatter|all-to-all|"
    r"collective-broadcast")
# an array in HLO text: element type, dimensions, layout
_ARRAY = re.compile(
    r"\b(pred|[su](?:8|16|32|64)|bf16|f16|f32|f64)\[([\d,]*)\](?:\{([^}]*)\})?")
_ITEM_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
               "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8,
               "f64": 8}
# a layout's memory space other than HBM's 0: on-chip memory (S(1): VMEM)
_ON_CHIP = re.compile(r"S\([1-9]\d*\)")


@dataclass
class Op:
    name: str  # HLO instruction text, as the trace names the event
    start_ns: float
    end_ns: float
    self_ns: float
    scope: str  # tf_op: the jit / named-scope path and the primitive
    category: str  # hlo_category
    leaf: bool = True  # no other event runs inside this one

    @property
    def short_name(self) -> str:
        """The instruction's name without its ``%`` and numeric suffix,
        e.g. ``_pallas_dia_spmv`` or ``fusion``."""
        head = self.name.split(" = ", 1)[0].lstrip("%")
        return re.sub(r"\.\d+$", "", head)

    @property
    def collective(self) -> bool:
        return bool(_COLLECTIVE.search(self.category)
                    or _COLLECTIVE.search(self.short_name))

    @property
    def hbm_bytes(self):
        """Bytes of the instruction's result and operands that the
        compiled program keeps in HBM, as the instruction's text in the
        trace lays them out; an array whose layout names an on-chip memory
        space (``S(1)``) was brought there before the instruction ran, and
        moves no HBM bytes inside it.  None where the text has no operand
        list."""
        parts = _result_and_operands(self.name)
        if parts is None:
            return None
        total = 0
        for m in _ARRAY.finditer(" ".join(parts)):
            if m.group(3) and _ON_CHIP.search(m.group(3)):
                continue
            count = 1
            for d in filter(None, m.group(2).split(",")):
                count *= int(d)
            total += count * _ITEM_BYTES[m.group(1)]
        return total


def _result_and_operands(text: str):
    """``(result shape, operand list)`` of ``%name = <shape> <op>(<operands>),
    <attributes>``, or None."""
    _, sep, rest = text.partition(" = ")
    if not sep:
        return None
    depth, result, tail = 0, None, ""
    for i, c in enumerate(rest):
        depth += (c in "([{") - (c in ")]}")
        if c == " " and depth == 0:
            result, tail = rest[:i], rest[i + 1:]
            break
    start = tail.find("(")
    if result is None or start < 0:
        return None
    depth = 0
    for i in range(start, len(tail)):
        depth += (tail[i] in "([{") - (tail[i] in ")]}")
        if depth == 0:
            return result, tail[start + 1:i]
    return None


@dataclass
class Chip:
    name: str
    ops: list  # every event in the window, outermost first at each start
    busy: list  # merged [start_ns, end_ns) intervals, clipped to the window

    @property
    def busy_ns(self) -> float:
        return sum(e - s for s, e in self.busy)


@dataclass
class Trace:
    window: tuple  # (start_ns, end_ns) of the bench_window span
    chips: list
    spans: list = field(default_factory=list)  # (name, start_ns, end_ns)

    @property
    def window_ns(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def busy_s(self) -> float:
        """Device busy seconds, averaged over the chips."""
        return sum(c.busy_ns for c in self.chips) / len(self.chips) / 1e9

    def self_ns(self, chip: int, keep) -> float:
        """Summed self time of the ops on ``chip`` for which ``keep(op)``."""
        return sum(op.self_ns for op in self.chips[chip].ops if keep(op))

    def breakdown(self, top: int = 10) -> dict:
        """The ops that took most self time on chip 0, summed by scope
        path, and the device's idle time summed by the host span that
        was innermost when each gap began."""
        chip = self.chips[0]
        by_scope = {}
        for op in chip.ops:
            key = op.scope or op.short_name
            by_scope[key] = by_scope.get(key, 0.0) + op.self_ns
        device_ops = sorted(by_scope.items(), key=lambda kv: -kv[1])[:top]
        gaps = {}
        t = self.window[0]
        for s, e in chip.busy + [(self.window[1], self.window[1])]:
            if s > t:
                label = self._host_span_at(t)
                n, ns = gaps.get(label, (0, 0.0))
                gaps[label] = (n + 1, ns + s - t)
            t = max(t, e)
        idle = sorted(gaps.items(), key=lambda kv: -kv[1][1])[:top]
        return {
            "device_ops": [[k, v / 1e9] for k, v in device_ops],
            "idle_gaps": [[f"{k} ({n} gaps)", ns / 1e9]
                          for k, (n, ns) in idle],
        }

    def _host_span_at(self, t: float) -> str:
        inside = [(e - s, name) for name, s, e in self.spans if s <= t < e]
        return min(inside)[1] if inside else "no bench span"


def _merge(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def _chip(name, line, meta, window) -> Chip:
    w0, w1 = window
    events = sorted(
        ((e.start_ns, e.start_ns + e.duration_ns, e.name)
         for e in line.events
         if e.start_ns < w1 and e.start_ns + e.duration_ns > w0),
        key=lambda t: (t[0], -t[1]),
    )
    ops, stack, child_ns = [], [], []
    for start, end, ev_name in events:
        while stack and ops[stack[-1]].end_ns <= start:
            stack.pop()
        if stack:
            child_ns[stack[-1]] += end - start
        md = meta.get(ev_name, {})
        ops.append(Op(ev_name, start, end, 0.0, md.get("tf_op", ""),
                      md.get("hlo_category", "")))
        child_ns.append(0.0)
        stack.append(len(ops) - 1)
    for op, c in zip(ops, child_ns):
        op.self_ns = max(0.0, op.end_ns - op.start_ns - c)
        op.leaf = c == 0
    busy = _merge((max(s, w0), min(e, w1)) for s, e, _ in events)
    return Chip(name, ops, busy)


def load(path: str) -> Trace:
    """Reduce the ``.xplane.pb`` at ``path`` (a file, or a profiler log
    directory holding exactly one)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        found = glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                          recursive=True)
        if len(found) != 1:
            raise ValueError(f"{len(found)} traces under {path}")
        path = found[0]
    with open(path, "rb") as f:
        data = f.read()
    meta = xspace.op_metadata(data)
    profile = ProfileData.from_serialized_xspace(data)
    spans = []
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend(
                    (e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events if e.name.startswith(SPAN_PREFIX))
    windows = [(s, e) for name, s, e in spans if name == WINDOW_SPAN]
    if len(windows) != 1:
        raise ValueError(f"{len(windows)} {WINDOW_SPAN} spans in {path}")
    window = windows[0]
    chips = []
    for plane in profile.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        for line in plane.lines:
            if line.name == _OPS_LINE:
                chips.append((int(m.group(1)), _chip(
                    plane.name, line, meta.get(plane.name, {}), window)))
    if not chips:
        raise ValueError(f"no TPU device plane in {path}")
    chips.sort(key=lambda kc: kc[0])
    return Trace(window, [c for _, c in chips], spans)
