"""Compile the main path's Pallas kernels and the PCG+Jacobi solve for a
TPU v5e, at the sizes chip_smoke.py runs, without a chip.

The TPU compiler is installed here and compiles for a described (not
attached) v5e: it refuses what interpret mode cannot see — slices not
aligned to the (8, 128) tiling, more VMEM than a kernel may use, a
program that does not fit the chip's 16 GB.  Nothing runs, so these
tests say nothing about results or times.

Everything chip-shaped is built inside fixtures and tests: describing
the topology loads libtpu, which must never happen while a module is
imported (the on-chip-measurement guide, section 2).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

_F32, _I32 = jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def chip(one_chip):
    """Shape factory on the described chip, configured as the chip
    runs: x64 off (the test tier turns it on; Mosaic cannot lower the
    kernels' 64-bit index maps), and the persistent compile cache off —
    an entry compiled for a described chip cannot be read back
    without one."""
    from jax.experimental.compilation_cache import compilation_cache

    prev = (jax.config.jax_enable_compilation_cache,
            jax.config.jax_enable_x64)
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_enable_x64", False)
    compilation_cache.reset_cache()
    try:
        yield lambda shape, dtype: jax.ShapeDtypeStruct(
            shape, dtype, sharding=one_chip
        )
    finally:
        jax.config.update("jax_enable_compilation_cache", prev[0])
        jax.config.update("jax_enable_x64", prev[1])
        compilation_cache.reset_cache()


def _poisson_offsets(side):
    return (-side * side, -side, -1, 0, 1, side, side * side)


def _custom_calls(compiled):
    return compiled.as_text().count("tpu_custom_call")


def test_dia_kernel_compiles_at_256cubed(chip):
    """The capi_pcg_jacobi SpMV: 7 diagonals, n = 16,777,216."""
    from amgx_tpu.ops.pallas_dia import _pallas_dia_spmv

    n = 256**3
    compiled = _pallas_dia_spmv.lower(
        chip((7, n), _F32), chip((n,), _F32), _poisson_offsets(256), n
    ).compile()
    assert _custom_calls(compiled) >= 1


def test_stencil_kernel_compiles_at_256cubed(chip):
    from amgx_tpu.ops.pallas_stencil import _pallas_stencil_spmv
    from amgx_tpu.ops.stencil import StencilMeta

    steps = ((0, 0, -1), (0, -1, 0), (-1, 0, 0), (0, 0, 0),
             (1, 0, 0), (0, 1, 0), (0, 0, 1))
    meta = StencilMeta(
        kind="const", grid=(256, 256, 256), steps=steps,
        offsets=tuple(dx + 256 * dy + 256 * 256 * dz
                      for dx, dy, dz in steps),
    )
    compiled = _pallas_stencil_spmv.lower(
        chip((7,), _F32), chip((256**3,), _F32), meta
    ).compile()
    assert _custom_calls(compiled) >= 1


def _compile_well(chip, n, w, W, k):
    """The windowed-ELL kernel at ``n`` rows of ``w`` slots, a ``W``-lane
    window and ``k``-entry touched-row lists."""
    from amgx_tpu.ops.pallas_well import (
        _LANE,
        _ROW_TILE,
        _SUB,
        _pallas_well_spmv,
    )

    nt = -(-n // _ROW_TILE)
    return _pallas_well_spmv.lower(
        chip((nt, _SUB, w * _LANE), _I32), chip((nt, _SUB, w * _LANE), _F32),
        chip((nt,), _I32), chip((nt, 1, k), _I32), chip((nt,), _I32),
        chip((n,), _F32), n, W,
    ).compile()


def test_windowed_ell_kernel_compiles_at_128cubed(chip):
    """Tiled-ELL shapes of a 128^3 7-point operator: 2048 row tiles of
    7 slots, the 27,648-lane window RCM gives it (rounded up to whole
    vreg tiles, 28,672) and at most 227 touched x rows a tile."""
    assert _custom_calls(_compile_well(chip, 128**3, 7, 28672, 227)) >= 1


@pytest.mark.parametrize(
    "n, w, W, k",
    [
        # level 1's A of the classical 128^3 hierarchy (RCM order)
        (649539, 37, 31744, 186),
        # the fine level's P of that hierarchy
        (2097152, 6, 342016, 279),
    ],
    ids=["A1", "P0"],
)
def test_windowed_ell_kernel_compiles_at_classical_shapes(chip, n, w, W, k):
    """The classical 128^3 hierarchy's windowed-ELL shapes: level 1's A
    and the fine P, whose window is the widest the gate admits there."""
    assert _custom_calls(_compile_well(chip, n, w, W, k)) >= 1


def test_pcg_jacobi_solve_compiles_at_256cubed(chip, monkeypatch):
    """The capi_pcg_jacobi solve program for 256^3, traced as on the
    chip (the backend gate is steered here, in the test), must
    dispatch the DIA kernel and fit the chip's 16 GB."""
    import amgx_tpu
    from amgx_tpu.config.amg_config import AMGConfig
    from amgx_tpu.core.matrix import SparseMatrix
    from amgx_tpu.io.poisson import poisson_3d_7pt
    from amgx_tpu.solvers import create_solver

    amgx_tpu.initialize()
    cfg = AMGConfig.from_string(
        '{"config_version": 2, "solver": {"scope": "main",'
        ' "solver": "PCG", "max_iters": 4000, "tolerance": 1e-4,'
        ' "monitor_residual": 1, "convergence": "RELATIVE_INI",'
        ' "preconditioner": {"scope": "jac", "solver": "BLOCK_JACOBI",'
        ' "relaxation_factor": 1.0, "max_iters": 1,'
        ' "monitor_residual": 0}}}'
    )
    solver = create_solver(cfg, "default")
    # the solver's structure (PCG over Jacobi over a DIA operator) from
    # a small system; the operator's arrays at 256^3 as shapes
    solver.setup(poisson_3d_7pt(16, dtype=np.float32))
    side = 256
    n = side**3
    nnz = 7 * n - 6 * side * side
    A = SparseMatrix(
        row_offsets=chip((n + 1,), _I32), col_indices=chip((nnz,), _I32),
        values=chip((nnz,), _F32), row_ids=chip((nnz,), _I32),
        diag=chip((n,), _F32), ell_cols=None, ell_vals=None,
        dia_vals=chip((7, n), _F32), diag_src=chip((n,), _I32),
        dia_src=chip((7, n), _I32), n_rows=n, n_cols=n,
        dia_offsets=_poisson_offsets(side),
    )
    params = (A, (A, chip((n,), _F32)))

    def paths(tree):
        return [jax.tree_util.keystr(p)
                for p, _ in jax.tree_util.tree_leaves_with_path(tree)]

    assert paths(params) == paths(solver.apply_params())
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = jax.jit(solver.make_solve()).lower(
        params, chip((n,), _F32), chip((n,), _F32)
    ).compile()
    assert _custom_calls(compiled) >= 1
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes)
    assert total < 16e9, f"{total / 1e9:.2f} GB does not fit a v5e"


def test_geo_transfers_compile_at_208cubed(chip):
    """The fine restriction and prolongation of the 208^3 SIZE_8
    aggregation hierarchy in the structured-transfer format: box sums
    and one MXU product each, no gather, and no temporary larger than
    the fine vector (a minor dimension of 2 would be padded 64x)."""
    from amgx_tpu.core.matrix import SparseMatrix
    from amgx_tpu.ops.geo_transfer import GeoTransferMeta
    from amgx_tpu.ops.spmv import spmv

    n, nc = 208**3, 104**3

    def transfer(transpose):
        rows, cols = (nc, n) if transpose else (n, nc)
        return SparseMatrix(
            row_offsets=chip((rows + 1,), _I32),
            col_indices=chip((n,), _I32), values=chip((n,), _F32),
            row_ids=chip((n,), _I32), diag=chip((rows,), _F32),
            ell_cols=None, ell_vals=None, n_rows=rows, n_cols=cols,
            geo_meta=GeoTransferMeta((208, 208, 208), (2, 2, 2),
                                     transpose),
        )

    R, P = transfer(True), transfer(False)
    legs = (
        jax.jit(lambda R, r: spmv(R, r)).lower(R, chip((n,), _F32)),
        jax.jit(lambda P, x, xc: x + spmv(P, xc)).lower(
            P, chip((n,), _F32), chip((nc,), _F32)),
    )
    for lowered in legs:
        compiled = lowered.compile()
        text = compiled.as_text()
        assert " gather(" not in text
        assert "convolution(" in text
        assert compiled.memory_analysis().temp_size_in_bytes <= 4 * n


def test_ell_gather_compiles_fast_at_classical_level2(chip):
    """The XLA ELL SpMV at level 2 of the 128^3 classical hierarchy
    (134,483 rows x 82 slots): gathered row-major, (n, w), the TPU
    compiler took ~130 s here; slot-major, a few seconds."""
    import time

    from amgx_tpu.ops.spmv import ell_spmv_xla

    n, w = 134_483, 82
    t0 = time.perf_counter()
    jax.jit(ell_spmv_xla).lower(
        chip((n, w), _I32), chip((n, w), _F32), chip((n,), _F32)
    ).compile()
    assert time.perf_counter() - t0 < 60
