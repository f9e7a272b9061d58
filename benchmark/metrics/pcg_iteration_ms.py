"""pcg_iteration_ms: the window's solve seconds over its Krylov iterations,
in milliseconds: the cost of one iteration (the outer SpMV, the dots and
one V-cycle), which the mix of iteration counts between right-hand sides
does not move."""


def read(run):
    iterations = sum(s["iterations"] for s in run.solves)
    if not iterations:
        return None
    return 1e3 * sum(s["seconds"] for s in run.solves) / iterations
