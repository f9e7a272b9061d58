"""fine_spmv_roofline: the fine level's SpMV kernel against the chip's HBM
bandwidth.  The fine operator's stored format (as the solver holds it)
names a file under ``benchmark/formats/``: the kernels that apply it.
Kernel events outside the coarser levels' scopes are the fine level's (the
outer Krylov loop and ``amg_l0_*``).  Each call's bytes are those of its
result and operands that the compiled program keeps in HBM, read from the
instruction in the trace (``trace.Op.hbm_bytes``): an operand that XLA has
brought on chip beforehand moves no HBM bytes inside the call.  The share
is the least time those bytes need at peak bandwidth over the time the
calls took; calls that move no HBM bytes are left out.  Bound: memory."""

import re

_COARSE = re.compile(r"(^|/)(amg_l[1-9]\d*_|amg_coarse_solve)")


def read(run):
    fmt = run.format_spec(run.structure.get("fine", {}).get("format"))
    if run.trace is None or fmt is None:
        return None
    moved = [(op.hbm_bytes, op.end_ns - op.start_ns)
             for op in run.trace.chips[0].ops
             if op.short_name in fmt["kernels"]
             and not _COARSE.search(op.scope)]
    moved = [(b, ns) for b, ns in moved if b]
    if not moved:
        return None
    least_s = sum(b for b, _ in moved) / run.peak["hbm_bytes_per_s"]
    took_s = sum(ns for _, ns in moved) / 1e9
    return 100.0 * least_s / took_s
