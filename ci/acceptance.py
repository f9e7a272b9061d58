"""Acceptance-matrix runner (BASELINE.md "Acceptance configurations").

Runs the five BASELINE acceptance configurations at sizes feasible on
the current backend and writes ACCEPTANCE.md with the iteration counts
and residual-rate table — the comparison discipline BASELINE.md:33-35
demands (iteration parity before wall-clock).  Sizes marked (reduced)
are scaled down from the official problem for CPU/virtual-mesh runs;
chip numbers come from the benchmark (``python3 -m benchmark.run``).

Usage:  python ci/acceptance.py [out.md]
"""

import contextlib
import io
import os
import sys
import warnings

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# CPU + virtual mesh unless the caller explicitly overrides (the
# session env pins a remote TPU platform that the acceptance sweep
# must not depend on)
_plat = os.environ.get("AMGX_ACCEPTANCE_PLATFORM", "cpu")
os.environ["JAX_PLATFORMS"] = _plat
if "xla_force_host_platform_device_count" not in os.environ.get(
    "XLA_FLAGS", ""
):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

jax.config.update("jax_platforms", _plat)
jax.config.update("jax_enable_x64", True)

import numpy as np

import amgx_tpu

amgx_tpu.initialize()

from amgx_tpu.config.amg_config import AMGConfig
from amgx_tpu.io.poisson import poisson_3d_7pt, poisson_rhs
from amgx_tpu.solvers import create_solver

CONFIG_DIR = "/root/reference/src/configs"
ROWS = []


def _rate(hist, iters):
    h = np.asarray(hist).max(axis=1)
    h = h[: iters + 1]
    h = h[np.isfinite(h)]
    if len(h) < 2 or h[0] <= 0:
        return float("nan")
    return float((h[-1] / h[0]) ** (1.0 / (len(h) - 1)))


def run_serial(label, cfg_path, A, b):
    cfg = AMGConfig.from_file(cfg_path)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        with contextlib.redirect_stdout(io.StringIO()):
            s = create_solver(cfg, "default")
            s.setup(A)
            res = s.solve(b)
    rel = float(
        np.linalg.norm(np.asarray(b) - A.to_scipy() @ np.asarray(res.x))
        / max(np.linalg.norm(np.asarray(b)), 1e-300)
    )
    ROWS.append(
        (
            label,
            int(res.iters),
            _rate(res.history, int(res.iters)),
            rel,
            "converged" if int(res.status) == 0 else f"status={int(res.status)}",
        )
    )


def main(out="ACCEPTANCE.md"):
    # 1. matrix.mtx + FGMRES_AGGREGATION (dDDI)
    from amgx_tpu.core.matrix import SparseMatrix as _SM
    from amgx_tpu.io.matrix_market import read_system

    sysd, rhs1, _sol1 = read_system("/root/reference/examples/matrix.mtx")
    A1 = _SM.from_coo(
        sysd["rows"], sysd["cols"], sysd["vals"],
        n_rows=sysd["n_rows"], n_cols=sysd["n_cols"],
        block_size=sysd["block_dims"][0],
    )
    b1 = rhs1 if rhs1 is not None else np.ones(A1.n_rows)
    run_serial(
        "1. FGMRES_AGGREGATION on matrix.mtx (dDDI)",
        os.path.join(CONFIG_DIR, "FGMRES_AGGREGATION.json"),
        A1, np.asarray(b1),
    )

    # 2. PCG + Jacobi, Poisson 48^3 (reduced from 256^3)
    A2 = poisson_3d_7pt(48)
    b2 = poisson_rhs(A2.n_rows)
    run_serial(
        "2. PCG+Jacobi Poisson 48^3 (reduced)",
        os.path.join(CONFIG_DIR, "PCG_CLASSICAL_V_JACOBI.json"),
        A2, b2,
    )

    # 3. Classical RS V-cycle PMIS+D1, Poisson 32^3 (reduced from 512^3)
    A3 = poisson_3d_7pt(32)
    b3 = poisson_rhs(A3.n_rows)
    run_serial(
        "3. AMG_CLASSICAL_PMIS V-cycle Poisson 32^3 (reduced)",
        os.path.join(CONFIG_DIR, "AMG_CLASSICAL_PMIS.json"),
        A3, b3,
    )

    # 4. GMRES(30) + multicolor-ILU0 on a nonsymmetric convection-
    # diffusion system (atmosmodd unavailable offline: zero-egress)
    import scipy.sparse as sps

    nx = 40
    n4 = nx * nx
    main_d = np.full(n4, 4.0)
    ex = np.full(n4 - 1, -1.0 + 0.4)
    wx = np.full(n4 - 1, -1.0 - 0.4)
    ex[nx - 1:: nx] = 0.0
    wx[nx - 1:: nx] = 0.0
    ey = np.full(n4 - nx, -1.0 + 0.25)
    wy = np.full(n4 - nx, -1.0 - 0.25)
    sp4 = sps.diags_array(
        [main_d, ex, wx, ey, wy], offsets=[0, 1, -1, nx, -nx]
    ).tocsr()
    from amgx_tpu.core.matrix import SparseMatrix

    A4 = SparseMatrix.from_scipy(sp4)
    b4 = poisson_rhs(n4)
    cfg4 = AMGConfig.from_string(
        '{"config_version": 2, "solver": {"scope": "main",'
        ' "solver": "GMRES", "gmres_n_restart": 30, "max_iters": 200,'
        ' "tolerance": 1e-8, "monitor_residual": 1,'
        ' "convergence": "RELATIVE_INI", "preconditioner":'
        ' {"scope": "ilu", "solver": "MULTICOLOR_ILU",'
        ' "max_iters": 1}}}'
    )
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        with contextlib.redirect_stdout(io.StringIO()):
            s4 = create_solver(cfg4, "default")
            s4.setup(A4)
            res4 = s4.solve(b4)
    rel4 = float(
        np.linalg.norm(b4 - sp4 @ np.asarray(res4.x))
        / np.linalg.norm(b4)
    )
    ROWS.append(
        (
            "4. GMRES(30)+ILU0 conv-diff 40^2 (atmosmodd substitute)",
            int(res4.iters), _rate(res4.history, int(res4.iters)),
            rel4,
            "converged" if int(res4.status) == 0
            else f"status={int(res4.status)}",
        )
    )

    # 5. Distributed aggregation AMG, 8-way partitioned Poisson7
    from jax.sharding import Mesh

    from amgx_tpu.distributed.amg import DistributedAMG

    devs = jax.devices()
    n_parts = min(8, len(devs))
    mesh = Mesh(np.array(devs[:n_parts]), ("x",))
    A5 = poisson_3d_7pt(32).to_scipy()
    b5 = poisson_rhs(A5.shape[0])
    amg = DistributedAMG(A5, mesh, consolidate_rows=1024)
    x5, it5, nrm5 = amg.solve(b5, max_iters=100, tol=1e-8)
    rel5 = float(
        np.linalg.norm(b5 - A5 @ x5) / np.linalg.norm(b5)
    )
    ROWS.append(
        (
            f"5. Distributed agg-AMG-PCG Poisson 32^3, {n_parts} shards "
            f"({len(amg.h.levels)} sharded levels)",
            it5, float("nan"), rel5,
            "converged" if rel5 < 1e-7 else "NOT converged",
        )
    )

    lines = [
        "# Acceptance matrix (BASELINE.md configurations)",
        "",
        "Produced by `python ci/acceptance.py` on backend "
        f"`{jax.default_backend()}` ({len(jax.devices())} devices). "
        "Sizes marked (reduced) are scaled down from the official "
        "problem for this backend; iteration counts are the parity "
        "contract (BASELINE.md:33-35).",
        "",
        "| configuration | iterations | avg rate | true rel residual |"
        " status |",
        "|---|---|---|---|---|",
    ]
    for label, it, rate, rel, st in ROWS:
        rate_s = "-" if np.isnan(rate) else f"{rate:.3f}"
        lines.append(
            f"| {label} | {it} | {rate_s} | {rel:.2e} | {st} |"
        )
    lines.append("")
    # preserve any hand-maintained appendix below the generated table
    # (e.g. the round-5 official-size section) across regenerations
    tail = ""
    try:
        with open(out) as f:
            prev = f.read()
        marker = "\n## "
        if marker in prev:
            tail = prev[prev.index(marker):]
    except FileNotFoundError:
        pass
    with open(out, "w") as f:
        f.write("\n".join(lines))
        if tail:
            f.write(tail)
    print("\n".join(lines) + tail)


if __name__ == "__main__":
    main(*sys.argv[1:])
