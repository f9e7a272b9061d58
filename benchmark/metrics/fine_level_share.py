"""fine_level_share: device self time under the finest level's V-cycle
scopes (``amg_l0_*``, amgx_tpu/amg/hierarchy.py) over the device's busy
time in the window, on chip 0.  The outer Krylov loop's own SpMV is not
in these scopes."""

import re

_FINE = re.compile(r"(^|/)amg_l0_")


def read(run):
    if run.trace is None:
        return None
    ns = run.trace.self_ns(0, lambda op: _FINE.search(op.scope))
    busy = run.trace.chips[0].busy_ns
    return 100.0 * ns / busy if ns and busy else None
