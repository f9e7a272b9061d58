"""Distributed SpMV and Krylov solves under shard_map.

The solve loop runs entirely inside ``shard_map`` over a device mesh.
Halo exchange (reference exchange_halo, comms_mpi_hostbuffer_stream.cu)
is one ``lax.ppermute`` per neighbor direction — B2L gather into a
per-direction send buffer, neighbor permute over ICI, halo scatter —
with comm volume O(boundary).  Partitions without a small neighbor-
direction set fall back to the all_gather pool (O(N·max_send)).
Reductions are ``psum`` (reference global_reduce).  The while_loop
condition uses the psum'd scalar, identical on every shard — SPMD.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from amgx_tpu.core.sharding import shard_map
from amgx_tpu.distributed.partition import DistributedMatrix
from amgx_tpu.ops import blas as blas_mod

# Collective-site accounting (trace-time, the PR 8 machinery):
#   * every cross-shard reduction (psum) records into BOTH the PR 8
#     reduction slot (ops/blas.reduction_counter — one psum IS one
#     global reduction) and the "psum_sites" slot serve/batched's
#     psum_site_counter reads, so the serve-side collective gates see
#     distributed solves with no extra plumbing;
#   * every halo exchange records into its own "halo_sites" slot —
#     ci/halo_bench.py gates the fine-level SpMV to <= 1 exchange per
#     apply (forward; the reverse exchange records the same site).
_record_psum_site, _psum_sites = blas_mod.make_site_counter(
    "psum_sites"
)
record_halo_exchange, halo_site_counter = blas_mod.make_site_counter(
    "halo_sites"
)


def _shard_params(A: DistributedMatrix, cfg=None, scope="default"):
    """Traced per-shard arrays, stacked on the shard axis: the local
    operator (interior/boundary split when built) plus halo-exchange
    maps, as a dict pytree.

    min_rows_latency_hiding (reference core.cu:346): when the config
    sets it explicitly, levels below the row threshold drop the
    interior/boundary overlap split (a negative explicit value drops
    it everywhere).  Unset, the TPU default keeps the overlap at every
    level — the split costs nothing under XLA's scheduler."""
    overlap_ok = True
    if cfg is not None and cfg.has("min_rows_latency_hiding", scope):
        thresh = int(cfg.get("min_rows_latency_hiding", scope))
        rows = int(A.ell_cols.shape[1]) if hasattr(
            A.ell_cols, "shape") else 0
        overlap_ok = thresh >= 0 and rows >= thresh
    out = {
        "diag": jnp.asarray(A.diag),
        "ell": (jnp.asarray(A.ell_cols), jnp.asarray(A.ell_vals)),
    }
    if A.int_mask is not None and overlap_ok:
        out["split"] = (
            jnp.asarray(A.int_mask),
            jnp.asarray(A.own_mask),
            None if A.bnd_rows is None else jnp.asarray(A.bnd_rows),
        )
    from amgx_tpu.ops.pallas_well import well_kernel_selected

    # ship the tiled copies only where the kernel actually runs —
    # they duplicate the ELL footprint in HBM
    if well_kernel_selected(A):
        out["wtile"] = (
            jnp.asarray(A.ell_wcols),
            jnp.asarray(A.ell_wvals),
            jnp.asarray(A.ell_wbase),
            jnp.asarray(A.ell_wrows),
            jnp.asarray(A.ell_wcount),
        )
    if A.uses_ppermute:
        out["ex"] = (
            tuple(jnp.asarray(s) for s in A.send_idx_d),
            jnp.asarray(A.halo_dir),
            jnp.asarray(A.halo_pos),
        )
    else:
        out["ex"] = (
            jnp.asarray(A.send_idx),
            jnp.asarray(A.halo_src_part),
            jnp.asarray(A.halo_src_pos),
        )
    return out


def exchange_halo(A: DistributedMatrix, shard, x_loc, axis):
    """halo values for x (reference exchange_halo_v2).  Runs inside
    shard_map; `shard` is the _shard_params dict with the leading
    shard axis dropped.  Block vectors ([rows, b]) exchange whole
    b-vectors per halo slot (reference block halo buffers)."""
    record_halo_exchange()
    blk = x_loc.ndim == 2
    if A.uses_ppermute:
        send_idx_d, halo_dir, halo_pos = shard["ex"]
        halo = jnp.zeros(
            (halo_pos.shape[0],) + x_loc.shape[1:], x_loc.dtype
        )
        for d, perm in enumerate(A.perms):
            buf = x_loc[send_idx_d[d]]
            recv = jax.lax.ppermute(buf, axis, perm=list(perm))
            sel = halo_dir == d
            if blk:
                sel = sel[:, None]
            halo = jnp.where(sel, recv[halo_pos], halo)
        return halo
    send_idx, hsp, hpos = shard["ex"]
    send = x_loc[send_idx]  # B2L gather
    pool = jax.lax.all_gather(send, axis)  # [N, max_send(, b)]
    return pool[hsp, hpos]


def exchange_halo_reverse(A: DistributedMatrix, shard, y_own, y_halo,
                          axis):
    """Accumulating reverse exchange (reference add_from_halo,
    distributed_comms.h:138): each shard's HALO-slot partials are sent
    back to the owning shard and ADDED into its owned slots.  This is
    the transpose of exchange_halo — classical restriction R = P^T
    scatters partial coarse sums into halo slots, which must fold back
    into their owners' rows.

    ``y_own``: [rows] owned partials; ``y_halo``: [max_halo] halo-slot
    partials.  Returns y_own with remote contributions added.
    """
    record_halo_exchange()
    if A.uses_ppermute:
        send_idx_d, halo_dir, halo_pos = shard["ex"]
        for d, perm in enumerate(A.perms):
            ms = send_idx_d[d].shape[0]
            # pack: this shard's halo partials for direction d land at
            # their position in the (src, dst) id list; others drop
            # into a spill slot
            buf = jnp.zeros((ms + 1,), y_own.dtype)
            idx = jnp.where(halo_dir == d, halo_pos, ms)
            buf = buf.at[idx].add(y_halo)
            inv = [(dst, src) for (src, dst) in perm]
            recv = jax.lax.ppermute(buf[:ms], axis, perm=inv)
            # unpack: the owner adds received partials at the same
            # B2L gather indices the forward exchange packs from.
            # INVARIANT: padding positions of send_idx_d are 0 and the
            # matching recv slots are provably 0 (y_halo padding only
            # ever receives zero-valued scatter contributions, and buf
            # slots beyond a pair's id count are never written), so
            # row 0 accumulates only zeros from padding.
            y_own = y_own.at[send_idx_d[d]].add(recv)
        return y_own
    send_idx, hsp, hpos = shard["ex"]
    pool = jax.lax.all_gather(y_halo, axis)  # [N, max_halo]
    hsp_all = jax.lax.all_gather(hsp, axis)  # [N, max_halo]
    hpos_all = jax.lax.all_gather(hpos, axis)
    me = jax.lax.axis_index(axis)
    ms = send_idx.shape[0]
    contrib = jnp.zeros((ms + 1,), y_own.dtype)
    idx = jnp.where(hsp_all == me, hpos_all, ms)
    contrib = contrib.at[idx.reshape(-1)].add(pool.reshape(-1))
    return y_own.at[send_idx].add(contrib[:ms])


def make_local_spmv(A: DistributedMatrix, axis):
    """Shard-local y = (A x)_loc with halo exchange over `axis`.

    Latency hiding (reference multiply.cu:95-110
    exchange_halo_split_gather -> interior -> boundary): the interior
    partial product reads only x_loc, so it carries no data dependence
    on the permute results — XLA's latency-hiding scheduler overlaps
    it with the in-flight exchange."""

    from amgx_tpu.ops.pallas_well import well_kernel_selected

    use_wtile = well_kernel_selected(A)  # matches _shard_params

    def spmv(shard, x_loc):
        ell_cols, ell_vals = shard["ell"]
        if A.block_size > 1:
            # block SpMV (reference bsrmv, multiply.cu:49-71): one
            # einsum contracts the b×b blocks — MXU-batched on TPU.
            # Same interior/boundary overlap structure as scalar.
            halo = exchange_halo(A, shard, x_loc, axis)
            if "split" in shard:
                int_mask, own_mask, bnd_rows = shard["split"]
                nloc = x_loc.shape[0]
                lc = jnp.minimum(ell_cols, nloc - 1)
                yi = jnp.where(
                    int_mask[:, None],
                    jnp.einsum("rwij,rwj->ri", ell_vals, x_loc[lc]),
                    0.0,
                )
                xf = jnp.concatenate([x_loc, halo])
                if bnd_rows is not None:
                    yb = jnp.einsum(
                        "rwij,rwj->ri",
                        ell_vals[bnd_rows],
                        xf[ell_cols[bnd_rows]],
                    )
                    y = jnp.concatenate(
                        [yi, jnp.zeros((1, yi.shape[1]), yi.dtype)]
                    )
                    y = y.at[bnd_rows].add(yb)
                    return y[:nloc]
                yb = jnp.where(
                    (own_mask & ~int_mask)[:, None],
                    jnp.einsum("rwij,rwj->ri", ell_vals, xf[ell_cols]),
                    0.0,
                )
                return yi + yb
            xf = jnp.concatenate([x_loc, halo])
            return jnp.einsum("rwij,rwj->ri", ell_vals, xf[ell_cols])
        if "split" in shard:
            int_mask, own_mask, bnd_rows = shard["split"]
            halo = exchange_halo(A, shard, x_loc, axis)
            if use_wtile:
                # interior pass on the Pallas windowed kernel: interior
                # columns are all local, so the gather reads only x_loc
                # — overlaps with the in-flight exchange.  Boundary/
                # padding rows carry zero values in the tiled arrays,
                # so the output needs no mask.
                from amgx_tpu.ops.pallas_well import _pallas_well_spmv

                wc, wv, wb, wr, wn = shard["wtile"]
                yi = _pallas_well_spmv(
                    wc, wv, wb, wr, wn, x_loc, x_loc.shape[0],
                    A.ell_wwidth,
                )
            else:
                # XLA interior pass: columns clamped into the local
                # range (the clamp only touches boundary rows, whose
                # contribution comes from the compact pass below) — no
                # dependence on the permute results, so it lands in a
                # fusion XLA can schedule DURING the exchange
                # (ci/check_overlap_hlo.py asserts the dataflow)
                nloc = x_loc.shape[0]
                lc = jnp.minimum(ell_cols, nloc - 1)
                yi = jnp.where(
                    int_mask, jnp.sum(ell_vals * x_loc[lc], axis=-1), 0
                )
            if bnd_rows is not None:
                # compact boundary pass (reference multiply.cu:95-110
                # boundary-rows kernel): gather the O(surface) boundary
                # rows, compute against [x_loc, halo], scatter-add into
                # a spill-padded copy of yi.  Structurally unfusable
                # with the interior reduce -> overlap-safe, and the
                # second pass costs O(nb*w) instead of O(rows*w).
                xf = jnp.concatenate([x_loc, halo])
                yb = jnp.sum(
                    ell_vals[bnd_rows] * xf[ell_cols[bnd_rows]],
                    axis=-1,
                )
                y = jnp.concatenate(
                    [yi, jnp.zeros((1,), yi.dtype)]
                )
                y = y.at[bnd_rows].add(yb)
                return y[: x_loc.shape[0]]
            xf = jnp.concatenate([x_loc, halo])
            yb = jnp.where(
                own_mask & ~int_mask,
                jnp.sum(ell_vals * xf[ell_cols], axis=-1),
                0,
            )
            return yi + yb
        halo = exchange_halo(A, shard, x_loc, axis)
        xf = jnp.concatenate([x_loc, halo])
        return jnp.sum(ell_vals * xf[ell_cols], axis=1)

    return spmv


def _pdot(a, b, axis):
    # vdot flattens, so block vectors [rows, b] reduce correctly.
    # One psum = one global reduction: counted into both the PR 8
    # reduction slot and the serve psum-site slot at trace time.
    blas_mod.record_reduction()
    _record_psum_site()
    return jax.lax.psum(jnp.vdot(a, b), axis)


def _pgram(L, Rt, axis):
    """Distributed fused Gram block: the shard-local
    :func:`amgx_tpu.ops.blas.gram_block` matmul followed by ONE psum —
    ALL inner products of an s-step outer iteration in a single
    collective (gram_block already records the reduction site; only
    the psum site is added here)."""
    from amgx_tpu.ops.blas import gram_block

    _record_psum_site()
    return jax.lax.psum(gram_block(L, Rt), axis)


def _safe_block_inv(d):
    """Batched b×b diagonal-block inverse with the scalar path's
    zero-diagonal protection: singular blocks (inv -> inf/nan) fall
    back to identity instead of poisoning the solve."""
    inv = jnp.linalg.inv(d)
    ok = jnp.isfinite(inv).all(axis=(-2, -1), keepdims=True)
    eye = jnp.eye(d.shape[-1], dtype=d.dtype)
    return jnp.where(ok, inv, eye)


def _run_dist_solve(A, b_global, mesh, max_iters, tol, preconditioned):
    axis = mesh.axis_names[0]
    shard = _shard_params(A)
    bp = jnp.asarray(A.pad_vector(b_global))
    local_spmv = make_local_spmv(A, axis)

    def local_solve(sh, b_loc):
        diag = sh["diag"]
        if A.block_size > 1:
            # block-Jacobi: batched b×b diagonal-block inverses
            # (reference block_jacobi_solver.cu setup); padding rows
            # carry identity blocks, and singular blocks fall back to
            # identity (the scalar d==0 guard's block analogue)
            dinv = _safe_block_inv(diag)
            prec = lambda rr: jnp.einsum("rij,rj->ri", dinv, rr)
        else:
            dinv = jnp.where(diag != 0, 1.0 / diag, 1.0)
            prec = lambda rr: dinv * rr
        x = jnp.zeros_like(b_loc)
        r = b_loc  # x0 = 0
        z = prec(r) if preconditioned else r
        p = z
        rho = _pdot(r, z, axis)
        nrm0 = jnp.sqrt(_pdot(b_loc, b_loc, axis))

        def cond(c):
            it, x, r, p, rho, nrm = c
            return (it < max_iters) & (nrm >= tol * nrm0) & (nrm0 > 0)

        def body(c):
            it, x, r, p, rho, nrm = c
            q = local_spmv(sh, p)
            alpha = rho / _pdot(p, q, axis)
            x = x + alpha * p
            r = r - alpha * q
            z = prec(r) if preconditioned else r
            rho_new = _pdot(r, z, axis)
            p = z + (rho_new / rho) * p
            nrm = jnp.sqrt(_pdot(r, r, axis))
            return (it + 1, x, r, p, rho_new, nrm)

        it, x, r, p, rho, nrm = jax.lax.while_loop(
            cond, body, (jnp.int32(0), x, r, p, rho, nrm0)
        )
        return x, it, nrm

    in_shard = jax.tree.map(lambda _: P(axis), shard)

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(in_shard, P(axis)),
        out_specs=(P(axis), P(), P()),
    )
    def solve_sm(shard_stk, b_stk):
        sh = jax.tree.map(lambda s: s[0], shard_stk)
        x, it, nrm = local_solve(sh, b_stk[0])
        return x[None], it, nrm

    x, it, nrm = jax.jit(solve_sm)(shard, bp)
    return A.unpad_vector(jax.device_get(x)), int(it), float(nrm)


def dist_pcg_jacobi(A: DistributedMatrix, b, mesh: Mesh, max_iters=200,
                    tol=1e-8):
    """Distributed Jacobi-PCG: returns (x, iters, final_norm)."""
    return _run_dist_solve(A, b, mesh, max_iters, tol, True)


def dist_cg(A: DistributedMatrix, b, mesh: Mesh, max_iters=200, tol=1e-8):
    return _run_dist_solve(A, b, mesh, max_iters, tol, False)


def dist_spmv_replicated_check(A: DistributedMatrix, x, mesh: Mesh):
    """y = A x through the distributed path (validation against the
    single-device SpMV — the distributed_io test pattern, SURVEY §4)."""
    axis = mesh.axis_names[0]
    shard = _shard_params(A)
    xp = jnp.asarray(A.pad_vector(x))
    local_spmv = make_local_spmv(A, axis)
    in_shard = jax.tree.map(lambda _: P(axis), shard)

    @functools.partial(
        shard_map,
        mesh=mesh,
        in_specs=(in_shard, P(axis)),
        out_specs=P(axis),
    )
    def spmv_sm(shard_stk, x_stk):
        sh = jax.tree.map(lambda s: s[0], shard_stk)
        return local_spmv(sh, x_stk[0])[None]

    y = jax.jit(spmv_sm)(shard, xp)
    return A.unpad_vector(jax.device_get(y))
