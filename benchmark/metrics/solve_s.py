"""solve_s: the summed wall time of the window's solves over their count;
each solve is timed from its dispatch until its solution is ready."""


def read(run):
    return sum(s["seconds"] for s in run.solves) / len(run.solves)
