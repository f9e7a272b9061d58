"""coarse_levels_share: device self time under the coarser levels' V-cycle
scopes (``amg_l<i>_*`` for i >= 1, and ``amg_coarse_solve``) over the
device's busy time in the window, on chip 0."""

import re

_COARSE = re.compile(r"(^|/)(amg_l[1-9]\d*_|amg_coarse_solve)")


def read(run):
    if run.trace is None:
        return None
    ns = run.trace.self_ns(0, lambda op: _COARSE.search(op.scope))
    busy = run.trace.chips[0].busy_ns
    return 100.0 * ns / busy if ns and busy else None
