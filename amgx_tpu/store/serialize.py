"""Versioned setup-artifact schema: a fully-set-up solver flattened to
an ``.npz`` payload plus a JSON manifest.

The serialized unit is the SETUP — the part AmgX treats as a throwaway
per-process cost and this store makes durable: every
:class:`~amgx_tpu.core.matrix.SparseMatrix` with all of its
acceleration structures and gather maps exactly as built (restore is a
load, not a rebuild), the full AMG level chain with R/P and the
numeric-Galerkin :class:`~amgx_tpu.amg.spgemm.RAPPlan` index lists,
and the solve-boundary scale/reorder vectors.  Smoother and
coarse-solver parameters re-derive deterministically from the
persisted level operators at import (their setup is O(n) device work;
the expensive, skipped part is coarsening + Galerkin products) — the
round-trip contract, enforced by tests/test_store.py, is that a
restored solver reproduces the original's iteration counts exactly.

Format: one ``.npz`` holding every array leaf under generated keys
plus a ``__manifest__`` JSON string; the manifest carries
``schema_version``, the solver identity (registry name, scope, the
full :class:`~amgx_tpu.config.amg_config.AMGConfig` state and its
content hash), the finest operator's ``(sparsity_fingerprint, dtype)``
store key, and the ``spec`` tree mapping the flattened structure back
to array keys.  Any schema change MUST bump ``SCHEMA_VERSION`` — the
store layer treats other versions as cache misses, never migrations.
"""

from __future__ import annotations

import json
import time

import numpy as np

from amgx_tpu.core.errors import StoreError

SCHEMA_VERSION = 1

# SparseMatrix array-leaf fields, serialized verbatim (csr + accel
# formats + gather maps); static metadata rides in the spec
_SMAT_ARRAY_FIELDS = (
    "row_offsets", "col_indices", "values", "row_ids", "diag",
    "ell_cols", "ell_vals", "ell_wcols", "ell_wvals", "ell_wbase",
    "ell_wrows", "ell_wcount",
    "dia_vals", "dense", "diag_src", "dia_src", "ell_src",
    "mf_coefs", "mf_src", "geo_w",
)


def _gather_np(src, values):
    """Host twin of :func:`amgx_tpu.core.matrix._gather_src`: rebuild
    a value-layout array (diag/ell_vals/dia_vals) from the persisted
    first-occurrence gather map (-1 = empty slot)."""
    v = values[np.clip(src, 0, None)]
    mask = (src >= 0).reshape(src.shape + (1,) * (values.ndim - 1))
    return np.where(mask, v, 0)


# ---------------------------------------------------------------------------
# tagged-tree flatten / unflatten


def flatten(tree):
    """Flatten a setup-state tree into ``(spec, arrays)``.

    ``spec`` is a JSON-able tag tree; ``arrays`` maps generated keys to
    the array leaves (device arrays still referenced, not copied —
    callers materialize with :func:`materialize` before writing).
    Handles None, python scalars/strings, tuples/lists, str-keyed
    dicts, numpy / JAX arrays, SparseMatrix, SpMMPlan and RAPPlan;
    anything else raises a typed :class:`StoreError` (the caller's cue
    that this setup is not persistable).
    """
    import jax

    from amgx_tpu.amg.spgemm import RAPPlan, SpMMPlan
    from amgx_tpu.core.matrix import SparseMatrix

    arrays: dict = {}
    # object-identity dedup: one solver tree references the same
    # matrix from several layers (a PCG's A IS its AMG preconditioner's
    # finest level), and serializing it once both shrinks the payload
    # and restores the sharing on load.  keepalive pins ids for the
    # duration of the walk.
    seen: dict = {}
    keepalive: list = []

    def rec(obj):
        if obj is None:
            return {"t": "none"}
        if isinstance(obj, (bool, str)):
            return {"t": "py", "v": obj}
        if isinstance(obj, (int, np.integer)):
            return {"t": "py", "v": int(obj)}
        if isinstance(obj, (float, np.floating)):
            return {"t": "py", "v": float(obj)}
        if isinstance(obj, (SparseMatrix, RAPPlan, SpMMPlan)) or (
            isinstance(obj, (np.ndarray, jax.Array))
        ):
            ref = seen.get(id(obj))
            if ref is not None:
                return {"t": "ref", "i": ref}
            idx = len(seen)
            seen[id(obj)] = idx
            keepalive.append(obj)
            if isinstance(obj, (np.ndarray, jax.Array)):
                key = f"a{len(arrays)}"
                arrays[key] = obj
                node = {
                    "t": "arr", "k": key,
                    "host": isinstance(obj, np.ndarray),
                }
                dt = np.dtype(obj.dtype)
                if dt.kind == "V":
                    # extension dtypes (ml_dtypes bfloat16 — mixed-
                    # precision hierarchies): npz round-trips them as
                    # raw void bytes, losing the dtype, so the spec
                    # records it and materialize/readers reinterpret
                    # through a same-width uint view.  Optional key —
                    # pre-policy payloads never carry it, so schema v1
                    # stays valid
                    node["dt"] = str(dt)
            elif isinstance(obj, SparseMatrix):
                node = _smat_spec(obj, rec)
            elif isinstance(obj, RAPPlan):
                node = {
                    "t": "rap", "ap": rec(obj.ap), "rap": rec(obj.rap)
                }
            else:
                node = {
                    "t": "spmm",
                    "left": rec(obj.left_idx),
                    "right": rec(obj.right_idx),
                    "out": rec(obj.out_idx),
                    "nnz_out": int(obj.nnz_out),
                }
            return {"t": "def", "i": idx, "n": node}
        if isinstance(obj, (tuple, list)):
            return {
                "t": "tuple" if isinstance(obj, tuple) else "list",
                "items": [rec(v) for v in obj],
            }
        if isinstance(obj, dict):
            if not all(isinstance(k, str) for k in obj):
                raise StoreError(
                    "setup state dict has non-string keys; not "
                    "persistable"
                )
            return {
                "t": "dict", "items": {k: rec(v) for k, v in obj.items()}
            }
        raise StoreError(
            f"non-serializable setup leaf: {type(obj).__name__}"
        )

    return rec(tree), arrays


def _smat_spec(A, rec):
    from amgx_tpu.core.types import ViewType

    if A.partition is not None:
        raise StoreError(
            "distributed (partitioned) matrices are not persistable"
        )
    # Value-LAYOUT arrays re-derive exactly from (values, gather map),
    # and the dense copy from the CSR triplet: persisting the structure
    # maps but rehydrating the value layouts at load roughly halves
    # payload bytes (the f64 layouts dwarf their i32 maps) — which is
    # most of restore time.  The gather rehydration leans on the same
    # canonical-CSR invariant replace_values already documents:
    # duplicate (row, col) entries, when present at all, are
    # zero-valued beyond the first.
    rebuild = {"dense": {"t": "dense_from_csr"},
               "row_ids": {"t": "row_ids_rebuild"}}
    for name, src in (
        ("diag", "diag_src"),
        ("ell_vals", "ell_src"),
        ("dia_vals", "dia_src"),
        ("mf_coefs", "mf_src"),
    ):
        if getattr(A, src) is not None:
            rebuild[name] = {"t": "gather_rebuild", "src": src}
    fields = {}
    for name in _SMAT_ARRAY_FIELDS:
        v = getattr(A, name)
        if v is None:
            fields[name] = None
        else:
            fields[name] = rebuild.get(name) or rec(v)
    views = None
    if A.views is not None:
        views = [
            [ViewType(vt).name, int(off), int(size)]
            for vt, (off, size) in A.views.items()
        ]
    return {
        "t": "smat",
        "fields": fields,
        "static": {
            "n_rows": int(A.n_rows),
            "n_cols": int(A.n_cols),
            "block_size": int(A.block_size),
            "dia_offsets": (
                None
                if A.dia_offsets is None
                else [int(o) for o in A.dia_offsets]
            ),
            "ell_wwidth": (
                None if A.ell_wwidth is None else int(A.ell_wwidth)
            ),
            # optional key (schema v1 stays valid, like "dt"): the
            # MATRIX_FREE stencil descriptor, JSON-flattened; the
            # coefficient state itself rehydrates from (values, mf_src)
            **(
                {
                    "mf_meta": {
                        "kind": A.mf_meta.kind,
                        "grid": [int(v) for v in A.mf_meta.grid],
                        "steps": [
                            [int(d) for d in s] for s in A.mf_meta.steps
                        ],
                        "offsets": [int(o) for o in A.mf_meta.offsets],
                        "axis": (
                            None
                            if A.mf_meta.axis is None
                            else int(A.mf_meta.axis)
                        ),
                    }
                }
                if A.mf_meta is not None
                else {}
            ),
            # optional key likewise: the structured grid transfer's
            # static description (its weights persist as "geo_w")
            **(
                {
                    "geo_meta": {
                        "grid": [int(v) for v in A.geo_meta.grid],
                        "block": [int(v) for v in A.geo_meta.block],
                        "transpose": bool(A.geo_meta.transpose),
                    }
                }
                if A.geo_meta is not None
                else {}
            ),
            "views": views,
        },
        # persisted when already memoized, so a restored matrix serves
        # its fingerprint without rehashing (replace_values propagates
        # it, core/matrix.py).  NOT computed here: flatten may run
        # under the serve template-solver lock, and hashing every
        # level's index arrays there would stall concurrent solves —
        # unmemoized matrices simply hash lazily after restore.
        "fp": getattr(A, "_fingerprint_cache", None),
    }


def unflatten(spec, arrays):
    """Inverse of :func:`flatten`; ``arrays`` is a mapping of key ->
    loaded numpy array (an open npz works).  Malformed specs raise
    :class:`StoreError`.

    Runs in two passes over the (single, shared) spec tree: a planning
    pass computes every device-bound host array — verbatim leaves plus
    the rehydrated value layouts (row_ids by expansion, diag/ell/dia
    by gather map, dense by CSR scatter) — and ships them in ONE
    batched ``jax.device_put`` (per-array puts cost ~0.5 ms each, the
    dominant restore cost for deep hierarchies); the build pass then
    constructs the object tree around the transferred buffers.
    """
    import jax

    from amgx_tpu.amg.spgemm import RAPPlan, SpMMPlan
    from amgx_tpu.core.matrix import SparseMatrix
    from amgx_tpu.core.types import ViewType

    def get_array(key, dt=None):
        try:
            a = np.asarray(arrays[key])
        except KeyError:
            raise StoreError(
                f"payload is missing array {key!r}"
            ) from None
        if dt:
            # extension-dtype reinterpretation (see flatten's "dt" tag)
            try:
                a = a.view(np.dtype(dt))
            except (TypeError, ValueError) as e:
                raise StoreError(
                    f"payload array {key!r} does not reinterpret as "
                    f"{dt!r}: {e}"
                ) from e
        return a

    # ---- pass 0: index def nodes so refs resolve anywhere ------------
    def_nodes: dict = {}

    def index_defs(sp):
        if isinstance(sp, dict):
            if sp.get("t") == "def":
                def_nodes[int(sp["i"])] = sp.get("n")
                index_defs(sp.get("n"))
            else:
                for v in sp.values():
                    index_defs(v)
        elif isinstance(sp, (list, tuple)):
            for v in sp:
                index_defs(v)

    index_defs(spec)

    # ---- pass 1: plan device transfers -------------------------------
    host_batch: list = []
    devmap: dict = {}  # id(spec node) -> index into host_batch

    def want_dev(node, a):
        devmap[id(node)] = len(host_batch)
        host_batch.append(a)

    def plan(sp):
        if not isinstance(sp, dict):
            return
        t = sp.get("t")
        if t == "def":
            plan(sp.get("n"))
        elif t == "arr":
            if not sp.get("host"):
                want_dev(sp, get_array(sp.get("k"), sp.get("dt")))
        elif t in ("tuple", "list"):
            for v in sp.get("items", ()):
                plan(v)
        elif t == "dict":
            for v in sp.get("items", {}).values():
                plan(v)
        elif t == "spmm":
            for k in ("left", "right", "out"):
                plan(sp.get(k))
        elif t == "rap":
            plan(sp.get("ap"))
            plan(sp.get("rap"))
        elif t == "smat":
            _plan_smat(sp)

    def _raw_field(fields, name):
        """Host numpy of a verbatim-persisted smat field (rehydration
        input).  def/ref wrappers (object-identity dedup) resolve
        through the def index, so a csr buffer shared with another
        matrix still hydrates this one."""
        fsp = fields.get(name)
        for _ in range(2):  # def -> node, ref -> def'd node
            if isinstance(fsp, dict) and fsp.get("t") == "def":
                fsp = fsp.get("n")
            elif isinstance(fsp, dict) and fsp.get("t") == "ref":
                fsp = def_nodes.get(int(fsp["i"]))
        if not isinstance(fsp, dict) or fsp.get("t") != "arr":
            raise StoreError(
                f"smat rehydration needs persisted {name!r}"
            )
        return get_array(fsp.get("k"), fsp.get("dt"))

    def _plan_smat(sp):
        st = sp.get("static") or {}
        fields = sp.get("fields") or {}
        lazy = []
        for fsp in fields.values():
            if fsp is None or not isinstance(fsp, dict):
                continue
            t2 = fsp.get("t")
            if t2 in ("row_ids_rebuild", "gather_rebuild",
                      "dense_from_csr"):
                lazy.append(fsp)
            else:
                plan(fsp)
        if not lazy:
            return
        vals = _raw_field(fields, "values")
        ro = _raw_field(fields, "row_offsets")
        row_ids = np.repeat(
            np.arange(int(st["n_rows"]), dtype=np.int32),
            np.diff(ro),
        )
        for fsp in lazy:
            t2 = fsp["t"]
            if t2 == "row_ids_rebuild":
                out = row_ids
            elif t2 == "gather_rebuild":
                out = _gather_np(_raw_field(fields, fsp["src"]), vals)
            else:  # dense_from_csr: the one scatter rebuild
                out = np.zeros(
                    (int(st["n_rows"]), int(st["n_cols"])), vals.dtype
                )
                np.add.at(
                    out,
                    (row_ids, _raw_field(fields, "col_indices")),
                    vals,
                )
            want_dev(fsp, out)

    try:
        plan(spec)
    except StoreError:
        raise
    except Exception as e:
        raise StoreError(f"malformed payload spec: {e}") from e
    devs = jax.device_put(host_batch) if host_batch else []

    # ---- pass 2: build the object tree -------------------------------
    defs: dict = {}

    def dev_of(sp):
        return devs[devmap[id(sp)]]

    def rec(sp):
        try:
            t = sp["t"]
        except (TypeError, KeyError):
            raise StoreError(f"malformed payload spec node: {sp!r}") \
                from None
        if t == "none":
            return None
        if t == "py":
            return sp["v"]
        if t == "def":
            val = rec(sp["n"])
            defs[int(sp["i"])] = val
            return val
        if t == "ref":
            try:
                return defs[int(sp["i"])]
            except KeyError:
                raise StoreError(
                    f"payload spec ref {sp.get('i')!r} precedes its "
                    "definition"
                ) from None
        if t == "arr":
            if sp.get("host"):
                # copy host-retained leaves: the fast npz reader hands
                # out zero-copy views into the WHOLE payload blob, and
                # a long-lived holder (a warm-booted PaddedPattern)
                # would otherwise pin every byte of it in host memory
                return np.array(get_array(sp["k"], sp.get("dt")))
            return dev_of(sp)
        if t == "tuple":
            return tuple(rec(v) for v in sp["items"])
        if t == "list":
            return [rec(v) for v in sp["items"]]
        if t == "dict":
            return {k: rec(v) for k, v in sp["items"].items()}
        if t == "spmm":
            return SpMMPlan(
                left_idx=rec(sp["left"]),
                right_idx=rec(sp["right"]),
                out_idx=rec(sp["out"]),
                nnz_out=int(sp["nnz_out"]),
            )
        if t == "rap":
            return RAPPlan(ap=rec(sp["ap"]), rap=rec(sp["rap"]))
        if t == "smat":
            st = sp["static"]
            kw = {}
            for name, fsp in sp["fields"].items():
                if fsp is None:
                    kw[name] = None
                elif fsp.get("t") in (
                    "row_ids_rebuild", "gather_rebuild",
                    "dense_from_csr",
                ):
                    kw[name] = dev_of(fsp)
                else:
                    kw[name] = rec(fsp)
            wwidth = st.get("ell_wwidth")
            if kw.get("ell_wcols") is not None and kw.get("ell_wrows") is None:
                # a store from before the touched-row lists: the matrix
                # rides the XLA gather over its plain ELL arrays
                kw.update(ell_wcols=None, ell_wvals=None, ell_wbase=None)
                wwidth = None
            views = None
            if st.get("views") is not None:
                views = {
                    ViewType[name]: (int(off), int(size))
                    for name, off, size in st["views"]
                }
            mf_meta = None
            if st.get("mf_meta") is not None:
                from amgx_tpu.ops.stencil import StencilMeta

                try:
                    mm = st["mf_meta"]
                    mf_meta = StencilMeta(
                        kind=str(mm["kind"]),
                        grid=tuple(int(v) for v in mm["grid"]),
                        steps=tuple(
                            tuple(int(d) for d in s)
                            for s in mm["steps"]
                        ),
                        offsets=tuple(int(o) for o in mm["offsets"]),
                        axis=(
                            None
                            if mm.get("axis") is None
                            else int(mm["axis"])
                        ),
                    )
                except (TypeError, ValueError, KeyError) as e:
                    raise StoreError(
                        f"malformed mf_meta in payload spec: {e}"
                    ) from e
            geo_meta = None
            if st.get("geo_meta") is not None:
                from amgx_tpu.ops.geo_transfer import GeoTransferMeta

                try:
                    gm = st["geo_meta"]
                    geo_meta = GeoTransferMeta(
                        grid=tuple(int(v) for v in gm["grid"]),
                        block=tuple(int(v) for v in gm["block"]),
                        transpose=bool(gm["transpose"]),
                    )
                except (TypeError, ValueError, KeyError) as e:
                    raise StoreError(
                        f"malformed geo_meta in payload spec: {e}"
                    ) from e
            A = SparseMatrix(
                mf_meta=mf_meta,
                geo_meta=geo_meta,
                n_rows=int(st["n_rows"]),
                n_cols=int(st["n_cols"]),
                block_size=int(st["block_size"]),
                dia_offsets=(
                    None
                    if st.get("dia_offsets") is None
                    else tuple(int(o) for o in st["dia_offsets"])
                ),
                ell_wwidth=wwidth,
                views=views,
                partition=None,
                **kw,
            )
            if sp.get("fp"):
                object.__setattr__(A, "_fingerprint_cache", sp["fp"])
            return A
        raise StoreError(f"unknown payload spec tag {t!r}")

    return rec(spec)


def materialize(arrays: dict) -> dict:
    """Device arrays -> host numpy (the one sync point of a save).
    Extension dtypes (bfloat16) are stored through a same-width uint
    view — npz would silently degrade them to raw void bytes — and
    reinterpreted on read via the spec's "dt" tag."""
    out = {}
    for k, v in arrays.items():
        a = np.asarray(v)
        if a.dtype.kind == "V" and a.dtype.names is None:
            a = a.view(np.dtype(f"u{a.dtype.itemsize}"))
        out[k] = a
    return out


# ---------------------------------------------------------------------------
# payload files


def write_payload(path, arrays: dict, manifest: dict):
    """One ``.npz`` with the manifest embedded as ``__manifest__``.
    Written through an open file object so numpy cannot append its own
    ``.npz`` suffix behind the caller's back."""
    blob = payload_bytes(arrays, manifest)
    with open(path, "wb") as f:
        f.write(blob)


def payload_bytes(arrays: dict, manifest: dict) -> bytes:
    import io

    buf = io.BytesIO()
    np.savez(
        buf,
        __manifest__=np.array(json.dumps(manifest)),
        **materialize(arrays),
    )
    return buf.getvalue()


def _fast_npz_arrays(blob: bytes) -> dict:
    """Zero-copy npz decode: npz members are ZIP_STORED, so each
    array's bytes live contiguously in the blob — locate them via the
    zip directory and ``np.frombuffer`` straight out of the buffer.
    This skips zipfile's chunked CRC read path, which dominates
    restore time for multi-MB hierarchies (~5x slower).  Any anomaly
    (compressed member, odd header) raises and the caller falls back
    to ``np.load``; digest verification in the store layer already
    guarantees integrity, so skipping CRCs loses nothing."""
    import io
    import struct
    import zipfile

    out = {}
    with zipfile.ZipFile(io.BytesIO(blob)) as zf:
        for info in zf.infolist():
            if info.compress_type != zipfile.ZIP_STORED:
                raise ValueError("compressed npz member")
            # local file header: 30 fixed bytes, name/extra lengths at
            # offsets 26/28 (the extra field can differ from the
            # central directory's — read the local one)
            ho = info.header_offset
            if blob[ho : ho + 4] != b"PK\x03\x04":
                raise ValueError("bad local header")
            nlen, elen = struct.unpack_from("<HH", blob, ho + 26)
            start = ho + 30 + nlen + elen
            # parse only the (small, 64-byte-aligned) .npy header — a
            # full member slice would copy every array's bytes once
            hdr_len = min(4096, info.file_size)
            f = io.BytesIO(blob[start : start + hdr_len])
            version = np.lib.format.read_magic(f)
            np.lib.format._check_version(version)
            shape, fortran, dtype = np.lib.format._read_array_header(
                f, version
            )
            if dtype.hasobject:
                raise ValueError("object array in payload")
            data_off = start + f.tell()
            count = int(np.prod(shape, dtype=np.int64)) if shape else 1
            a = np.frombuffer(
                blob, dtype=dtype, count=count, offset=data_off
            )
            a = a.reshape(shape, order="F" if fortran else "C")
            name = info.filename
            if name.endswith(".npy"):
                name = name[: -len(".npy")]
            out[name] = a
    return out


def read_payload(path_or_bytes):
    """(arrays, manifest) from a payload file path or raw bytes.
    Anything unreadable — truncated file, not an npz, missing/broken
    manifest — raises :class:`StoreError` (the store layer converts
    that to a miss)."""
    import io

    if isinstance(path_or_bytes, (bytes, bytearray)):
        blob = bytes(path_or_bytes)
    else:
        try:
            with open(path_or_bytes, "rb") as f:
                blob = f.read()
        except OSError as e:
            raise StoreError(f"unreadable setup payload: {e}") from e
    try:
        arrays = _fast_npz_arrays(blob)
    except Exception:
        try:
            with np.load(io.BytesIO(blob), allow_pickle=False) as z:
                arrays = {k: z[k] for k in z.files}
        except Exception as e:
            raise StoreError(f"unreadable setup payload: {e}") from e
    m = arrays.pop("__manifest__", None)
    if m is None:
        raise StoreError("setup payload lacks a manifest")
    try:
        manifest = json.loads(str(m[()]))
    except Exception as e:
        raise StoreError(f"corrupt payload manifest: {e}") from e
    if not isinstance(manifest, dict):
        raise StoreError("corrupt payload manifest: not an object")
    return arrays, manifest


def check_schema(manifest: dict):
    v = manifest.get("schema_version")
    if v != SCHEMA_VERSION:
        raise StoreError(
            f"setup payload schema_version {v!r} != "
            f"{SCHEMA_VERSION} (stale or future schema)"
        )


# ---------------------------------------------------------------------------
# solver-level save / load


def solver_meta(solver) -> dict:
    """Identity half of the manifest: enough to re-instantiate the
    solver object (class, scope, config) and to key the store
    (fingerprint, config hash, dtype, schema version)."""
    if solver.A is None:
        raise StoreError("save_setup before setup()")
    fp, dtype_s = solver.A.setup_key()
    return {
        "schema_version": SCHEMA_VERSION,
        "kind": "solver_setup",
        "solver": solver.registry_name,
        "scope": solver.scope,
        # exact solve-boundary behavior flags (make_nested neutralizes
        # them on nested/template solvers; restore must preserve that)
        "scaling": solver.scaling,
        "reordering": solver.reordering,
        "config": solver.cfg.to_state(),
        "config_hash": solver.cfg.content_hash(),
        "fingerprint": fp,
        "dtype": dtype_s,
        "n_rows": int(solver.A.n_rows),
        "nnz": int(solver.A.nnz),
        "block_size": int(solver.A.block_size),
        "created_unix": time.time(),
    }


def build_solver(manifest: dict, tree, cfg=None):
    """Re-instantiate and restore a solver from an unflattened setup
    tree.  ``cfg=None`` reconstructs the persisted AMGConfig; passing
    one asserts content-hash compatibility (a hierarchy built under a
    different config would silently solve differently — the exact
    wrong-answer class the store must never produce)."""
    # registry side effects — same imports the service build path does
    import amgx_tpu.amg  # noqa: F401
    import amgx_tpu.solvers  # noqa: F401
    from amgx_tpu.config.amg_config import AMGConfig
    from amgx_tpu.solvers.registry import SolverRegistry

    if cfg is None:
        try:
            cfg = AMGConfig.from_state(manifest["config"])
        except Exception as e:  # typed: a garbled manifest is a
            # payload defect, not a configuration error
            raise StoreError(
                f"corrupt payload manifest: bad config state ({e})"
            ) from e
    elif cfg.content_hash() != manifest.get("config_hash"):
        raise StoreError(
            "setup payload was built under a different solver "
            "configuration (config_hash mismatch)"
        )
    try:
        cls = SolverRegistry.get(str(manifest["solver"]))
    except KeyError as e:
        raise StoreError(str(e)) from None
    solver = cls(cfg, str(manifest.get("scope", "default")))
    solver.scaling = str(manifest.get("scaling", solver.scaling))
    solver.reordering = str(
        manifest.get("reordering", solver.reordering)
    )
    t0 = time.perf_counter()
    solver._import_setup(tree)
    # memo-parity audit (PR 9): payloads written before the
    # meta-before-flatten ordering (or through _export_setup callers
    # that never fingerprinted) restore WITHOUT the structure memo,
    # so the first replace_values/serve submit on the restored
    # operator would rehash the pattern a cold-built one already
    # carries — reattach it from the manifest, which recorded the
    # same matrix's fingerprint at save time
    A = solver.A
    if (
        A is not None
        and getattr(A, "_fingerprint_cache", None) is None
        and manifest.get("fingerprint")
    ):
        object.__setattr__(
            A, "_fingerprint_cache", str(manifest["fingerprint"])
        )
    solver.restore_time = time.perf_counter() - t0
    return solver


def save_setup(solver, path) -> dict:
    """Persist a set-up solver to ``path``; returns the manifest."""
    # meta BEFORE flatten (same order as the serve-entry exporter):
    # solver_meta's setup_key() memoizes the finest operator's
    # fingerprint, so _smat_spec persists it and the restored matrix
    # serves replace_values/serve submits without rehashing — the
    # restore path propagates memos exactly like a cold-built solver
    manifest = solver_meta(solver)
    tree = solver._export_setup()
    spec, arrays = flatten(tree)
    manifest["spec"] = spec
    write_payload(path, arrays, manifest)
    return manifest


def load_setup(path, cfg=None, expect_dtype=None):
    """Restore a solver saved by :func:`save_setup` — without
    re-running setup.  Raises :class:`StoreError` on corrupt payloads
    or schema/config mismatches.

    ``expect_dtype`` gates the persisted operator dtype BEFORE the
    restore ships anything to the device (the C API's mode contract:
    a mixed-precision hierarchy would silently break the
    identical-iterations promise); the mismatch error carries
    ``RC_BAD_MODE`` so the API boundary reports the right code."""
    arrays, manifest = read_payload(path)
    check_schema(manifest)
    if manifest.get("kind") != "solver_setup":
        raise StoreError(
            f"payload kind {manifest.get('kind')!r} is not a solver "
            "setup"
        )
    if expect_dtype is not None:
        from amgx_tpu.core.errors import RC_BAD_MODE

        want = np.dtype(expect_dtype)
        try:
            got = np.dtype(str(manifest.get("dtype")))
        except TypeError:
            raise StoreError(
                f"corrupt payload manifest: bad dtype "
                f"{manifest.get('dtype')!r}"
            ) from None
        if got != want:
            raise StoreError(
                f"persisted setup is {got}, caller expects {want}",
                rc=RC_BAD_MODE,
            )
    tree = unflatten(manifest.get("spec"), arrays)
    return build_solver(manifest, tree, cfg=cfg)
