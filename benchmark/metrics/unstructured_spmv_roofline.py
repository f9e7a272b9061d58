"""unstructured_spmv_roofline: the unstructured SpMV path against the
chip's HBM bandwidth.  Its ops are those whose scope path holds
``spmv_ell`` (the XLA gather over ELL arrays), ``spmv_well`` (the
windowed-ELL Pallas kernel) or ``spmv_csr`` (gather and ``segment_sum``)
at any level of the V-cycle, P and R included, and in the Krylov loop
(amgx_tpu/ops/spmv.py; ``benchmark/formats/ELL.json``, ``ELLw.json``).
Each op's bytes are those of its result and operands that the compiled
program keeps in HBM, read from its instruction in the trace
(``trace.Op.hbm_bytes``).  The share is the least time those bytes need
at peak bandwidth over the self time the ops took, on chip 0; ops that
move no HBM bytes are left out.  Bound: memory."""

import re

UNSTRUCTURED = re.compile(r"(^|/)spmv_(ell|well|csr)(/|$)")


def read(run):
    if run.trace is None:
        return None
    moved = [(op.hbm_bytes, op.self_ns) for op in run.trace.chips[0].ops
             if UNSTRUCTURED.search(op.scope)]
    moved = [(b, ns) for b, ns in moved if b]
    took_s = sum(ns for _, ns in moved) / 1e9
    if not took_s:
        return None
    least_s = sum(b for b, _ in moved) / run.peak["hbm_bytes_per_s"]
    return 100.0 * least_s / took_s
