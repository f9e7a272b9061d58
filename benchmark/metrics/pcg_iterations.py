"""pcg_iterations: the Krylov iterations of the window's solves, averaged."""


def read(run):
    return sum(s["iterations"] for s in run.solves) / len(run.solves)
