"""unstructured_spmv_ms: device self time of the unstructured SpMV path
(ops whose scope path holds ``spmv_ell``, ``spmv_well`` or ``spmv_csr``,
every level and transfer included) on chip 0, over the window's Krylov
iterations, in milliseconds: what the gathers cost one iteration."""

from benchmark.metrics.unstructured_spmv_roofline import UNSTRUCTURED


def read(run):
    if run.trace is None:
        return None
    ns = run.trace.self_ns(0, lambda op: UNSTRUCTURED.search(op.scope))
    iterations = sum(s["iterations"] for s in run.solves)
    return ns / 1e6 / iterations if ns and iterations else None
