"""Multi-host launch plumbing and per-process partition construction.

Reference parity: the per-rank side of DistributedManager's upload path
(distributed_manager.cu loadDistributedMatrix*: each MPI rank holds its
own row block, renumbers to local with appended halo slots, and builds
B2L maps from neighbor metadata).  TPU shape:

  * :func:`initialize` wraps ``jax.distributed.initialize`` — after it,
    ``jax.devices()`` spans every host's chips and one ``Mesh`` over
    them drives the same shard_map code path as single-host.
  * :func:`local_part_from_rows` localizes ONE process's contiguous row
    block using only the block itself plus the global partition
    offsets — the global matrix is never materialized anywhere.
  * :func:`partition_from_local_parts` assembles the
    :class:`DistributedMatrix` from the per-part localized blocks in
    ONE process (stacked numpy arrays) — the single-host test shape.
  * :func:`sharded_partition` is the true multi-host assembly: each
    process materializes only its own parts' device arrays, the
    exchange plan rides an allgather of the O(boundary) halo-id
    lists, and the stacked arrays are ``jax.Array``s sharded one part
    per mesh device.  Tests validate bit-equality against the
    global-matrix path.
"""

from __future__ import annotations

import numpy as np

from amgx_tpu.distributed.partition import (
    DistributedMatrix,
    build_exchange_plan,
    finalize_partition,
)


def initialize(
    coordinator_address=None,
    num_processes=None,
    process_id=None,
    local_device_ids=None,
):
    """Join (or no-op) a multi-process JAX runtime.

    Explicit arguments always initialize.  With no arguments, the
    cluster autodetection of ``jax.distributed.initialize`` runs only
    when a recognized launcher environment is present (coordinator
    env vars, SLURM multi-task, TPU pod); otherwise this is a no-op so
    single-process use never touches the backend.  Call before any
    other JAX usage on every host.
    """
    import os

    import jax

    if coordinator_address is None and num_processes in (None, 1):
        markers = (
            "JAX_COORDINATOR_ADDRESS",
            "COORDINATOR_ADDRESS",
            "MEGASCALE_COORDINATOR_ADDRESS",
            "CLOUD_TPU_TASK_ID",
        )
        slurm_multi = int(os.environ.get("SLURM_NTASKS", "1") or 1) > 1
        if not (any(k in os.environ for k in markers) or slurm_multi):
            return  # single process
        try:
            jax.distributed.initialize()
        except RuntimeError:
            _reraise_unless_initialized(jax)
        return
    try:
        jax.distributed.initialize(
            coordinator_address=coordinator_address,
            num_processes=num_processes,
            process_id=process_id,
            local_device_ids=local_device_ids,
        )
    except RuntimeError:
        _reraise_unless_initialized(jax)


def _reraise_unless_initialized(jax):
    """Double-init is idempotent; anything else (wrong coordinator,
    connect/barrier timeout — jaxlib raises RuntimeError subclasses for
    those too) must propagate, or this process would silently continue
    on a single-process runtime and wedge the other hosts at the first
    collective."""
    is_init = getattr(jax.distributed, "is_initialized", None)
    if is_init is not None:
        if not is_init():
            raise
        return
    state = getattr(jax.distributed, "global_state", None)  # older jax
    if state is None or getattr(state, "client", None) is None:
        raise


def local_part_from_rows(
    indptr, gcols, vals, part_offsets, my_part, rows_pp=None
):
    """Localize one process's contiguous row block.

    indptr/gcols/vals: CSR of rows [part_offsets[p], part_offsets[p+1])
    with GLOBAL column ids.  Returns the localized part dict
    (owned-first columns, halo slots appended) consumed by
    :func:`partition_from_local_parts` — the same shape
    ``localize_columns`` produces from the global matrix.
    """
    import scipy.sparse as sps

    part_offsets = np.asarray(part_offsets, dtype=np.int64)
    p = int(my_part)
    lo, hi = int(part_offsets[p]), int(part_offsets[p + 1])
    assert np.asarray(indptr).shape[0] - 1 == hi - lo, (
        "row block != partition size"
    )
    # canonicalize (the global-path partitioner sort_indices()es first;
    # bit-equality of the ELL slot order depends on it)
    blk = sps.csr_matrix(
        (np.asarray(vals), np.asarray(gcols, dtype=np.int64),
         np.asarray(indptr)),
        shape=(hi - lo, int(part_offsets[-1])),
    )
    blk.sort_indices()
    indptr = blk.indptr
    gcols = blk.indices.astype(np.int64)
    vals = blk.data
    if rows_pp is None:
        rows_pp = int((part_offsets[1:] - part_offsets[:-1]).max())
    own = (gcols >= lo) & (gcols < hi)
    from amgx_tpu.distributed.partition import halo_localize

    cols, halo_glob = halo_localize(
        gcols, own, (gcols[own] - lo).astype(np.int32), rows_pp
    )
    return dict(
        indptr=indptr, cols=cols, vals=vals, halo_glob=halo_glob,
        rows_pp=int(rows_pp),
    )


def partition_from_local_parts(
    parts, part_offsets, proc_grid=None
) -> DistributedMatrix:
    """Assemble the exchange plan from per-part localized blocks.

    ``parts[p]`` is :func:`local_part_from_rows`'s output for part p.
    This assembly is single-process (it stacks every part's localized
    CSR into the [N, rows, w] device arrays); the true multi-host
    assembly — per-process slices + the halo-id allgather — is
    :func:`sharded_partition`.
    """
    part_offsets = np.asarray(part_offsets, dtype=np.int64)
    n_parts = len(parts)
    assert part_offsets.shape[0] == n_parts + 1
    n = int(part_offsets[-1])
    counts = (part_offsets[1:] - part_offsets[:-1]).astype(np.int64)
    rows_pp = int(counts.max())
    for p, part in enumerate(parts):
        got = part.get("rows_pp", rows_pp)
        if got != rows_pp:
            raise ValueError(
                f"part {p} localized with rows_pp={got}, assembly "
                f"expects {rows_pp}: halo column ids would be wrong"
            )
    owner = np.repeat(
        np.arange(n_parts, dtype=np.int32), counts
    )
    local_of = (
        np.arange(n, dtype=np.int64) - part_offsets[owner]
    ).astype(np.int32)
    return finalize_partition(
        parts, owner, local_of, counts, n, n_parts, proc_grid
    )



def sharded_partition(
    local_parts: dict,
    part_offsets,
    mesh,
    proc_grid=None,
) -> DistributedMatrix:
    """Multi-host assembly: each process materializes ONLY its own
    parts' device arrays; the exchange plan is built (replicated) from
    the allgathered O(boundary) halo-id lists.

    ``local_parts`` maps part index -> :func:`local_part_from_rows`
    output for the parts whose mesh device is addressable from this
    process (single-host: all of them).  The returned
    :class:`DistributedMatrix` carries stacked ``jax.Array``s sharded
    over ``mesh``'s first axis — drop-in for the shard_map solve path,
    with per-process memory O(n_global / n_hosts).

    Reference parity: the per-rank side of upload_all_global
    (distributed_manager.cu loadDistributedMatrix*) where each rank
    uploads only its block and halo plumbing is exchanged
    (distributed_arranger.h create_B2L et al.).

    Thin wrapper over :func:`assemble_level_sharded` (the same
    assembly serves every hierarchy level): validates the fine-level
    contract (uniform contiguous blocks, consistent rows_pp) and
    builds the comm fabric matching the mesh placement.
    """
    import jax

    from amgx_tpu.distributed.comm import AllgatherComm, LoopbackComm
    from amgx_tpu.distributed.partition import OffsetOwnership

    part_offsets = np.asarray(part_offsets, dtype=np.int64)
    n_parts = part_offsets.shape[0] - 1
    counts = (part_offsets[1:] - part_offsets[:-1]).astype(np.int64)
    rows_pp = int(counts.max())
    devices = mesh.devices.reshape(-1)
    if len(devices) != n_parts:
        raise ValueError(
            f"mesh has {len(devices)} devices, partition has "
            f"{n_parts} parts"
        )
    if not _uniform_blocks(part_offsets, rows_pp):
        raise ValueError(
            "sharded_partition needs uniform contiguous row blocks "
            "(rows_pp per part); got offsets "
            f"{part_offsets.tolist()}"
        )
    for p, part in local_parts.items():
        got = part.get("rows_pp", rows_pp)
        if got != rows_pp:
            raise ValueError(
                f"part {p} localized with rows_pp={got}, assembly "
                f"expects {rows_pp}: halo column ids would be wrong"
            )
    if jax.process_count() > 1:
        comm = AllgatherComm(n_parts, sorted(local_parts))
    else:
        comm = LoopbackComm(n_parts)
    return assemble_level_sharded(
        local_parts, OffsetOwnership(part_offsets), comm, mesh,
        proc_grid=proc_grid,
    )


def _part_boundary_count(part, count_p, rows_pp) -> int:
    """Number of owned rows referencing halo columns (>= rows_pp) in
    one localized part dict."""
    indptr = np.asarray(part["indptr"])
    cols = np.asarray(part["cols"])
    if cols.size == 0:
        return 0
    lens = np.diff(indptr)[:count_p]
    rid = np.repeat(np.arange(count_p), lens)
    hal = cols[: int(indptr[count_p])] >= rows_pp
    return int(np.unique(rid[hal]).size)


def stack_parts_sharded(
    per_part: dict, mesh, n_parts, dtype=None, shape=None
):
    """Stack per-part arrays into one [n_parts, ...] ``jax.Array``
    sharded one part per device of ``mesh``'s flattened device list.

    ``per_part[p]`` must be supplied for exactly the parts whose mesh
    device is addressable from this process (jax.Array invariant:
    every addressable shard needs a leaf).  All parts must share one
    shape+dtype; a process never materializes another part's data —
    the per-process memory stays O(global / n_processes).  A process
    addressing no parts passes an empty dict with explicit
    ``shape``+``dtype`` (the global array metadata must still agree).
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    devices = mesh.devices.reshape(-1)
    axis = mesh.axis_names[0]
    if per_part:
        some = np.asarray(next(iter(per_part.values())))
        if shape is None:
            shape = some.shape
        if dtype is None:
            dtype = some.dtype
    elif shape is None or dtype is None:
        raise ValueError(
            "a process holding no parts must pass explicit shape and "
            "dtype so the global array metadata agrees across processes"
        )
    leaves = [
        jax.device_put(
            np.ascontiguousarray(np.asarray(per_part[p]))[None],
            devices[p],
        )
        for p in sorted(per_part)
    ]
    return jax.make_array_from_single_device_arrays(
        (n_parts,) + tuple(shape),
        NamedSharding(mesh, P(axis)),
        leaves,
        dtype=np.dtype(dtype),
    )


def addressable_parts(mesh) -> list:
    """Part indices whose mesh device is addressable by this process
    (part p <-> flattened mesh device p — the assembly convention)."""
    import jax

    pid = jax.process_index()
    return [
        p
        for p, d in enumerate(mesh.devices.reshape(-1))
        if d.process_index == pid
    ]


def assemble_level_sharded(
    parts_by_p: dict, own, comm, mesh, proc_grid=None
):
    """Multi-process device assembly of ONE hierarchy level.

    The per-rank analogue of ``finalize_partition`` (reference: each
    rank assembles only its own level-matrix block, amg.cu:425-660
    setup_v2 + distributed_manager.cu reorder/B2L plumbing): this
    process materializes device arrays for ``parts_by_p``'s parts
    only; the exchange plan is built replicated from the allgathered
    O(boundary) halo-id lists riding the setup ``comm`` fabric.  Every
    stacked field of the returned :class:`DistributedMatrix` is a
    ``jax.Array`` sharded one part per mesh device — drop-in for the
    shard_map solve path.

    Bit-parity contract: per-part blocks are produced by the same
    ``part_ell_arrays`` / ``part_interior_windowed`` helpers as the
    single-process path, so shard p's slice equals the Loopback
    build's ``ell_*[p]`` exactly (asserted by the multiprocess test).
    """
    import jax

    from amgx_tpu.distributed.partition import (
        part_ell_arrays,
        pad_touched_rows,
        part_interior_windowed,
        tiled_ell_wanted,
    )

    if not own.offset_blocks:
        raise ValueError(
            "sharded level assembly needs analytic offset-block "
            "ownership (OffsetOwnership); arbitrary partition vectors "
            "must assemble single-process"
        )
    n_parts = own.n_parts
    counts = np.asarray(own.counts, dtype=np.int64)
    rows_pp = max(int(counts.max()), 1)
    mine = addressable_parts(mesh)
    if sorted(parts_by_p) != mine:
        raise ValueError(
            f"process drives parts {sorted(parts_by_p)} but addresses "
            f"mesh devices of parts {mine}: the comm striping must "
            "match the mesh placement (one part per mesh device)"
        )

    # ---- replicated plan from allgathered O(boundary) metadata ------
    from amgx_tpu.core.matrix import sparsity_fingerprint

    local_meta = {
        p: dict(
            halo_glob=np.asarray(part["halo_glob"], dtype=np.int64),
            w=int(np.diff(part["indptr"]).max(initial=0)),
            dtype=np.dtype(part["vals"].dtype).str,
            nb=int(_part_boundary_count(part, counts[p], rows_pp)),
            # per-shard pattern key (core.matrix.sparsity_fingerprint,
            # the serve cache's content hash) — O(local) to compute,
            # O(1) to gather; every process then holds the full tuple
            # so DistributedMatrix.fingerprint agrees replicated.
            # block_size is literally 1: this assembly path is
            # scalar-only (from_local_parts raises for blocks), which
            # keeps the key identical to finalize_partition's for any
            # pattern both paths can actually build
            fp=sparsity_fingerprint(
                np.asarray(part["indptr"]),
                np.asarray(part["cols"]),
                np.asarray(part["indptr"]).shape[0] - 1,
                rows_pp + len(part["halo_glob"]),
                1,
            ),
        )
        for p, part in parts_by_p.items()
    }
    meta = comm.allgather(local_meta, kind="level-meta")
    dm_plan, fb = build_exchange_plan(
        [meta[p]["halo_glob"] for p in range(n_parts)],
        own.owner_of,
        own.local_of_ids,
        n_parts,
    )
    w = max(max(meta[p]["w"] for p in range(n_parts)), 1)
    max_nb = max(meta[p]["nb"] for p in range(n_parts))
    dtype = np.dtype(meta[0]["dtype"])

    from amgx_tpu.distributed.partition import pack_boundary_rows

    # ---- per-part device blocks (same helpers as single-process) ----
    per_dev = {}
    for p, part in parts_by_p.items():
        ec, ev, dg = part_ell_arrays(part, rows_pp, w, dtype)
        is_bnd = (ec >= rows_pp).any(axis=1)
        own_m = np.zeros(rows_pp, dtype=bool)
        own_m[: counts[p]] = True
        per_dev[p] = dict(
            ell_cols=ec, ell_vals=ev, diag=dg,
            own_mask=own_m, int_mask=own_m & ~is_bnd,
            bnd_rows=pack_boundary_rows(
                [np.nonzero(own_m & is_bnd)[0]], rows_pp, max_nb
            )[0],
        )

    # windowed interior tiling: the static window width and touched-
    # row list length must agree across shards, so the local ones ride
    # one scalar allgather
    wwidth, wk = None, 1
    if tiled_ell_wanted(dtype):
        for p, part in parts_by_p.items():
            per_dev[p]["wtile"] = part_interior_windowed(
                part, per_dev[p]["ell_cols"], per_dev[p]["ell_vals"],
                per_dev[p]["int_mask"], rows_pp, counts[p],
            )
        shapes = comm.allgather(
            {
                p: (None if per_dev[p]["wtile"] is None
                    else (int(per_dev[p]["wtile"].width),
                          int(per_dev[p]["wtile"].rows.shape[2])))
                for p in parts_by_p
            },
            kind="wtile-width",
        )
        if all(s is not None for s in shapes):
            wwidth = max(W for W, _ in shapes)
            wk = max(k for _, k in shapes)
            for p in parts_by_p:
                wt = per_dev[p]["wtile"]
                per_dev[p]["ell_wcols"] = wt.cols
                per_dev[p]["ell_wvals"] = wt.vals
                per_dev[p]["ell_wbase"] = wt.bases
                per_dev[p]["ell_wrows"] = pad_touched_rows(wt.rows, wk)
                per_dev[p]["ell_wcount"] = wt.counts

    # explicit shapes/dtypes so a process holding no parts (its mesh
    # devices all remote) still constructs agreeing global arrays
    from amgx_tpu.ops.pallas_well import _LANE, _ROW_TILE, _SUB

    nt = -(-rows_pp // _ROW_TILE)
    spec = {
        "ell_cols": ((rows_pp, w), np.int32),
        "ell_vals": ((rows_pp, w), dtype),
        "diag": ((rows_pp,), dtype),
        "own_mask": ((rows_pp,), np.bool_),
        "int_mask": ((rows_pp,), np.bool_),
        "bnd_rows": ((max(max_nb, 1),), np.int32),
        "ell_wcols": ((nt, _SUB, w * _LANE), np.int32),
        "ell_wvals": ((nt, _SUB, w * _LANE), dtype),
        "ell_wbase": ((nt,), np.int32),
        "ell_wrows": ((nt, 1, wk), np.int32),
        "ell_wcount": ((nt,), np.int32),
    }

    def stack(key):
        shp_dt = spec.get(key)
        return stack_parts_sharded(
            {p: per_dev[p][key] for p in per_dev}, mesh, n_parts,
            shape=None if shp_dt is None else shp_dt[0],
            dtype=None if shp_dt is None else shp_dt[1],
        )

    # plan arrays are replicated numpy [N, ...]; ship each part's row
    # to its device so the traced lps pytree is fully sharded
    def stack_plan(arr):
        arr = np.asarray(arr)
        return stack_parts_sharded(
            {p: arr[p] for p in per_dev}, mesh, n_parts,
            shape=arr.shape[1:], dtype=arr.dtype,
        )

    return DistributedMatrix(
        n_global=int(own.n_global),
        n_parts=n_parts,
        rows_per_part=rows_pp,
        ell_cols=stack("ell_cols"),
        ell_vals=stack("ell_vals"),
        diag=stack("diag"),
        int_mask=stack("int_mask"),
        own_mask=stack("own_mask"),
        bnd_rows=stack("bnd_rows"),
        ell_wcols=None if wwidth is None else stack("ell_wcols"),
        ell_wvals=None if wwidth is None else stack("ell_wvals"),
        ell_wbase=None if wwidth is None else stack("ell_wbase"),
        ell_wrows=None if wwidth is None else stack("ell_wrows"),
        ell_wcount=None if wwidth is None else stack("ell_wcount"),
        ell_wwidth=wwidth,
        perms=None if dm_plan is None else dm_plan["perms"],
        send_idx_d=(
            None if dm_plan is None
            else tuple(stack_plan(s) for s in dm_plan["send_idx_d"])
        ),
        halo_dir=(
            None if dm_plan is None else stack_plan(dm_plan["halo_dir"])
        ),
        halo_pos=(
            None if dm_plan is None else stack_plan(dm_plan["halo_pos"])
        ),
        send_idx=stack_plan(fb["send_idx"]),
        halo_src_part=stack_plan(fb["halo_src_part"]),
        halo_src_pos=stack_plan(fb["halo_src_pos"]),
        max_send=fb["max_send"],
        max_halo=fb["max_halo"],
        owner=None,
        local_of=None,
        n_owned=counts.astype(np.int32),
        proc_grid=proc_grid,
        shard_fps=tuple(meta[p]["fp"] for p in range(n_parts)),
    )


def _uniform_blocks(part_offsets, rows_pp) -> bool:
    """True when every part (except possibly the last) owns exactly
    rows_pp contiguous rows — then pad/unpad work without the O(N)
    owner/local_of arrays (DistributedMatrix's owner=None layout)."""
    po = np.asarray(part_offsets, dtype=np.int64)
    expect = np.minimum(np.arange(len(po)) * rows_pp, po[-1])
    return bool(np.array_equal(po, expect))


