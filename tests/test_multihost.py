"""Per-process partition construction (multi-host plumbing).

Reference parity: the rank-local side of the distributed upload
(distributed_manager.cu loadDistributedMatrix*): each rank localizes
its own rows; no process holds the global matrix.
"""

import numpy as np
import pytest

import amgx_tpu
from amgx_tpu.distributed import partition_matrix
from amgx_tpu.distributed.multihost import (
    local_part_from_rows,
    partition_from_local_parts,
)
from amgx_tpu.io.poisson import poisson_3d_7pt, poisson_rhs

amgx_tpu.initialize()


def _offsets(n, n_parts):
    return np.linspace(0, n, n_parts + 1).astype(np.int64)


def test_local_parts_match_global_path():
    """Assembling from per-process row blocks reproduces the
    global-matrix partitioner bit-for-bit (contiguous partitions)."""
    sp = poisson_3d_7pt(10).to_scipy().tocsr()
    n = sp.shape[0]
    n_parts = 4
    offs = _offsets(n, n_parts)
    D_ref = partition_matrix(sp, n_parts)

    parts = []
    for p in range(n_parts):
        blk = sp[offs[p]:offs[p + 1]].tocsr()  # "this process's rows"
        parts.append(
            local_part_from_rows(
                blk.indptr, blk.indices, blk.data, offs, p
            )
        )
    D = partition_from_local_parts(parts, offs)

    np.testing.assert_array_equal(D.ell_cols, D_ref.ell_cols)
    np.testing.assert_allclose(D.ell_vals, D_ref.ell_vals)
    np.testing.assert_allclose(D.diag, D_ref.diag)
    assert D.uses_ppermute == D_ref.uses_ppermute
    if D.uses_ppermute:
        assert D.perms == D_ref.perms
        for a, b in zip(D.send_idx_d, D_ref.send_idx_d):
            np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(D.halo_dir, D_ref.halo_dir)
        np.testing.assert_array_equal(D.halo_pos, D_ref.halo_pos)


def test_local_parts_solve_on_mesh():
    import jax
    from jax.sharding import Mesh

    from amgx_tpu.distributed.solve import dist_pcg_jacobi

    sp = poisson_3d_7pt(8).to_scipy().tocsr()
    n = sp.shape[0]
    n_parts = 8
    offs = _offsets(n, n_parts)
    parts = [
        local_part_from_rows(
            sp[offs[p]:offs[p + 1]].tocsr().indptr,
            sp[offs[p]:offs[p + 1]].tocsr().indices,
            sp[offs[p]:offs[p + 1]].tocsr().data,
            offs, p,
        )
        for p in range(n_parts)
    ]
    D = partition_from_local_parts(parts, offs)
    b = poisson_rhs(n)
    mesh = Mesh(np.array(jax.devices()[:n_parts]), ("x",))
    x, iters, nrm = dist_pcg_jacobi(D, b, mesh, max_iters=60, tol=1e-8)
    rel = np.linalg.norm(b - sp @ x) / np.linalg.norm(b)
    assert rel < 1e-7, (rel, iters)


def test_row_block_size_mismatch_rejected():
    sp = poisson_3d_7pt(6).to_scipy().tocsr()
    offs = _offsets(sp.shape[0], 2)
    blk = sp[0:10].tocsr()  # wrong size for partition 0
    with pytest.raises(AssertionError):
        local_part_from_rows(blk.indptr, blk.indices, blk.data, offs, 0)


def test_rows_pp_mismatch_rejected():
    sp = poisson_3d_7pt(6).to_scipy().tocsr()
    n = sp.shape[0]
    offs = _offsets(n, 2)
    blk0 = sp[offs[0]:offs[1]].tocsr()
    blk1 = sp[offs[1]:offs[2]].tocsr()
    p0 = local_part_from_rows(
        blk0.indptr, blk0.indices, blk0.data, offs, 0, rows_pp=4096
    )
    p1 = local_part_from_rows(blk1.indptr, blk1.indices, blk1.data, offs, 1)
    with pytest.raises(ValueError):
        partition_from_local_parts([p0, p1], offs)


def test_unsorted_row_block_canonicalized():
    """Non-canonical (unsorted-indices) CSR input still reproduces the
    global path bit-for-bit."""
    sp = poisson_3d_7pt(8).to_scipy().tocsr()
    n = sp.shape[0]
    offs = _offsets(n, 2)
    D_ref = partition_matrix(sp, 2)
    parts = []
    for p in range(2):
        blk = sp[offs[p]:offs[p + 1]].tocoo()
        # reversed entry order per row -> unsorted indices in CSR
        order = np.lexsort((-blk.col, blk.row))
        indptr = np.zeros(int(offs[p + 1] - offs[p]) + 1, np.int64)
        np.add.at(indptr[1:], blk.row[order], 1)
        indptr = np.cumsum(indptr)
        parts.append(
            local_part_from_rows(
                indptr, blk.col[order], blk.data[order], offs, p
            )
        )
    D = partition_from_local_parts(parts, offs)
    np.testing.assert_array_equal(D.ell_cols, D_ref.ell_cols)
    np.testing.assert_allclose(D.ell_vals, D_ref.ell_vals)


def test_sharded_partition_matches_global_path():
    """The sharded assembly (per-part device arrays + plan from the
    allgathered halo lists alone) reproduces the global-path plan
    bit-for-bit and places one part per mesh device."""
    import jax
    from jax.sharding import Mesh

    from amgx_tpu.distributed.multihost import sharded_partition

    sp = poisson_3d_7pt(8).to_scipy().tocsr()
    n = sp.shape[0]
    n_parts = 8
    offs = np.arange(n_parts + 1, dtype=np.int64) * (-(-n // n_parts))
    offs[-1] = n
    owner = np.minimum(
        np.arange(n, dtype=np.int64) // int(offs[1]), n_parts - 1
    ).astype(np.int32)
    D_ref = partition_matrix(sp, n_parts, owner=owner)

    parts = {}
    for p in range(n_parts):
        blk = sp[offs[p]:offs[p + 1]].tocsr()
        parts[p] = local_part_from_rows(
            blk.indptr, blk.indices, blk.data, offs, p
        )
    mesh = Mesh(np.array(jax.devices()[:n_parts]), ("x",))
    D = sharded_partition(parts, offs, mesh)

    # plan parity with the global partitioner
    assert D.uses_ppermute == D_ref.uses_ppermute
    if D.uses_ppermute:
        assert D.perms == D_ref.perms
        for a, b in zip(D.send_idx_d, D_ref.send_idx_d):
            np.testing.assert_array_equal(a, b)
        np.testing.assert_array_equal(D.halo_dir, D_ref.halo_dir)
        np.testing.assert_array_equal(D.halo_pos, D_ref.halo_pos)
    np.testing.assert_array_equal(D.send_idx, D_ref.send_idx)
    np.testing.assert_array_equal(D.halo_src_part, D_ref.halo_src_part)
    np.testing.assert_array_equal(D.halo_src_pos, D_ref.halo_src_pos)

    # stacked arrays equal and sharded one part per device
    np.testing.assert_array_equal(np.asarray(D.ell_cols), D_ref.ell_cols)
    np.testing.assert_allclose(np.asarray(D.ell_vals), D_ref.ell_vals)
    np.testing.assert_allclose(np.asarray(D.diag), D_ref.diag)
    np.testing.assert_array_equal(np.asarray(D.int_mask), D_ref.int_mask)
    shards = {
        s.device: s.index[0] for s in D.ell_vals.addressable_shards
    }
    assert len(shards) == n_parts
    for p, dev in enumerate(mesh.devices.reshape(-1)):
        assert shards[dev] == slice(p, p + 1, None)


def test_sharded_partition_solves_on_mesh():
    import jax
    from jax.sharding import Mesh

    from amgx_tpu.distributed.multihost import sharded_partition
    from amgx_tpu.distributed.solve import (
        dist_pcg_jacobi,
        dist_spmv_replicated_check,
    )

    sp = poisson_3d_7pt(8).to_scipy().tocsr()
    n = sp.shape[0]
    n_parts = 8
    offs = np.arange(n_parts + 1, dtype=np.int64) * (-(-n // n_parts))
    offs[-1] = n
    parts = {}
    for p in range(n_parts):
        blk = sp[offs[p]:offs[p + 1]].tocsr()
        parts[p] = local_part_from_rows(
            blk.indptr, blk.indices, blk.data, offs, p
        )
    mesh = Mesh(np.array(jax.devices()[:n_parts]), ("x",))
    D = sharded_partition(parts, offs, mesh)

    rng = np.random.default_rng(7)
    x = rng.standard_normal(n)
    np.testing.assert_allclose(
        dist_spmv_replicated_check(D, x, mesh), sp @ x, rtol=1e-10
    )
    b = poisson_rhs(n)
    xs, iters, nrm = dist_pcg_jacobi(D, b, mesh, max_iters=60, tol=1e-8)
    rel = np.linalg.norm(b - sp @ xs) / np.linalg.norm(b)
    assert rel < 1e-7, (rel, iters)


def test_sharded_partition_rejects_nonuniform_blocks():
    import jax
    from jax.sharding import Mesh

    from amgx_tpu.distributed.multihost import sharded_partition

    sp = poisson_3d_7pt(6).to_scipy().tocsr()
    n = sp.shape[0]
    offs = np.array([0, 100, n], dtype=np.int64)  # 100 vs 116 rows
    parts = {}
    for p in range(2):
        blk = sp[offs[p]:offs[p + 1]].tocsr()
        parts[p] = local_part_from_rows(
            blk.indptr, blk.indices, blk.data, offs, p,
            rows_pp=int(np.diff(offs).max()),
        )
    mesh = Mesh(np.array(jax.devices()[:2]), ("x",))
    with pytest.raises(ValueError):
        sharded_partition(parts, offs, mesh)


def test_sharded_partition_windowed_interior(monkeypatch):
    """The sharded assembly builds the windowed-tiled interior arrays
    (agreed W across shards) matching the global-path build."""
    import jax
    from jax.sharding import Mesh

    from amgx_tpu.distributed.multihost import sharded_partition

    monkeypatch.setenv("AMGX_TPU_TILED_ELL", "1")
    sp = poisson_3d_7pt(8, dtype=np.float32).to_scipy().tocsr()
    n = sp.shape[0]
    n_parts = 4
    offs = np.arange(n_parts + 1, dtype=np.int64) * (n // n_parts)
    owner = (np.arange(n, dtype=np.int64) // (n // n_parts)).astype(
        np.int32
    )
    D_ref = partition_matrix(sp.astype(np.float32), n_parts, owner=owner)
    parts = {}
    for p in range(n_parts):
        blk = sp[offs[p]:offs[p + 1]].tocsr()
        parts[p] = local_part_from_rows(
            blk.indptr, blk.indices, blk.data, offs, p
        )
    mesh = Mesh(np.array(jax.devices()[:n_parts]), ("x",))
    D = sharded_partition(parts, offs, mesh)
    assert D_ref.ell_wcols is not None
    assert D.ell_wwidth == D_ref.ell_wwidth
    np.testing.assert_array_equal(np.asarray(D.ell_wcols), D_ref.ell_wcols)
    np.testing.assert_allclose(np.asarray(D.ell_wvals), D_ref.ell_wvals)
    np.testing.assert_array_equal(np.asarray(D.ell_wbase), D_ref.ell_wbase)
    np.testing.assert_array_equal(np.asarray(D.ell_wrows), D_ref.ell_wrows)
    np.testing.assert_array_equal(np.asarray(D.ell_wcount),
                                  D_ref.ell_wcount)


def test_interior_windowed_arrays(monkeypatch):
    """TPU-prep: the distributed partitioner builds windowed-tiled
    interior arrays whose Pallas kernel output (interpret mode) equals
    the XLA interior pass."""
    monkeypatch.setenv("AMGX_TPU_TILED_ELL", "1")
    sp = poisson_3d_7pt(10, dtype=np.float32).to_scipy().tocsr()
    n = sp.shape[0]
    D = partition_matrix(sp.astype(np.float32), 4)
    assert D.ell_wcols is not None and D.ell_wwidth is not None
    from amgx_tpu.ops.pallas_well import _pallas_well_spmv

    rng = np.random.default_rng(2)
    for p in range(4):
        x_loc = rng.standard_normal(D.rows_per_part).astype(np.float32)
        yi_ref = np.where(
            D.int_mask[p],
            (D.ell_vals[p] * np.where(
                D.ell_cols[p] < D.rows_per_part,
                x_loc[np.minimum(D.ell_cols[p], D.rows_per_part - 1)],
                0.0,
            )).sum(axis=1),
            0.0,
        )
        yi = np.asarray(_pallas_well_spmv(
            D.ell_wcols[p], D.ell_wvals[p], D.ell_wbase[p],
            D.ell_wrows[p], D.ell_wcount[p],
            x_loc, D.rows_per_part, D.ell_wwidth, interpret=True,
        ))
        np.testing.assert_allclose(yi, yi_ref, rtol=2e-4, atol=2e-4)


def test_interior_windowed_solve_matches_xla(monkeypatch):
    """The row-sharded Jacobi-PCG with its interior pass on the
    windowed kernel (interpret mode, taken as on a TPU) gives the x of
    the XLA interior pass."""
    import jax
    from jax.sharding import Mesh

    from amgx_tpu.core import sharding
    from amgx_tpu.distributed.solve import dist_pcg_jacobi
    from amgx_tpu.ops import pallas_well

    monkeypatch.setenv("AMGX_TPU_TILED_ELL", "1")
    sp = poisson_3d_7pt(10, dtype=np.float32).to_scipy().tocsr()
    D = partition_matrix(sp.astype(np.float32), 4)
    assert D.ell_wrows is not None
    mesh = Mesh(np.array(jax.devices()[:4]), ("x",))
    b = np.random.default_rng(3).standard_normal(sp.shape[0]).astype(
        np.float32)
    x_xla, it_xla, _ = dist_pcg_jacobi(D, b, mesh, tol=1e-6)
    # as on a TPU: the kernel, and shard_map without variance checks
    # (core/sharding.py)
    monkeypatch.setattr(pallas_well, "well_kernel_selected", lambda A: True)
    traced = []

    def kernel(*args, **kw):
        traced.append(args[5].shape)
        return well(*args, interpret=True, **kw)

    well = pallas_well._pallas_well_spmv
    monkeypatch.setattr(pallas_well, "_pallas_well_spmv", kernel)
    monkeypatch.setattr(sharding, "on_tpu", lambda: True)
    x_well, it_well, _ = dist_pcg_jacobi(D, b, mesh, tol=1e-6)
    assert traced  # the interior pass ran on the kernel
    assert it_well == it_xla
    np.testing.assert_allclose(x_well, x_xla, rtol=1e-5, atol=1e-6)
