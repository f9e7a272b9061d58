"""Loop ``closed``: one solve at a time, each on the next right-hand side of
the ring from x0 = 0, until the window has passed; a solve that starts in
the window runs to its end.  Each solve is timed from its dispatch until
its solution is ready."""

from __future__ import annotations

import time


def drive(program, seconds: float, traffic: dict, annotate) -> list:
    """``[(ring index, seconds, handle)]`` of the window's solves."""
    count = int(traffic["rhs_count"])
    done = []
    t_window = time.perf_counter()
    while time.perf_counter() - t_window < seconds:
        k = len(done) % count
        t0 = time.perf_counter()
        with annotate("bench_solve"):
            h = program.solve(k)
        with annotate("bench_block"):
            program.block(h)
        done.append((k, time.perf_counter() - t0, h))
    return done
