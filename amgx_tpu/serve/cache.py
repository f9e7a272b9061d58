"""Hierarchy cache: one solver setup per (sparsity fingerprint, config)
pair, shared by every request that reuses the pattern.

This is the service-side generalization of ``AMGX_solver_resetup`` /
``structure_reuse_levels``: the reference lets ONE solver object reuse
its setup across coefficient swaps; the cache lets EVERY request with a
matching sparsity fingerprint reuse one setup — AMG coarsening,
colorings, Galerkin plans, LU factors — with per-request coefficients
flowing through the traced batch-params rebuild
(:mod:`amgx_tpu.serve.batched`).

Cache semantics follow the reference's structure-reuse contract: the
hierarchy STRUCTURE (aggregates / C-F splitting / transfer-operator
weights) is the one computed from the first-seen coefficient set; later
coefficient sets re-evaluate the Galerkin chain values only.  Callers
whose coefficients drift far from the setup set should evict (the cache
is LRU-bounded) or use a fresh service.
"""

from __future__ import annotations

import concurrent.futures
import dataclasses
import threading
from collections import OrderedDict
from typing import Callable, Optional

from amgx_tpu.core.errors import ResourceError
from amgx_tpu.serve.bucketing import PaddedPattern
from amgx_tpu.serve.metrics import ServeMetrics


def config_hash(cfg) -> str:
    """Stable content hash of an AMGConfig (scoped key/value map).
    Canonical implementation lives on the config itself so the
    artifact store (:mod:`amgx_tpu.store`) can key persisted setups
    identically without importing the serve layer."""
    return cfg.content_hash()


@dataclasses.dataclass
class HierarchyEntry:
    """One cached setup: the template solver, its batch template, and
    the batched solve fn (unjitted — the service's compile cache owns
    jitting, keyed by shape bucket)."""

    solver: object  # set-up Solver (on the padded template matrix)
    template: object  # batch-params template pytree (None: no fast path)
    batch_fn: Optional[Callable]  # fn(template, vals_B, b_B, x0_B)
    signature: object  # hashable shape signature of the template pytree
    pattern: PaddedPattern
    # serializes resetup+solve on the SHARED template solver (the
    # sequential fallback and quarantine-reuse paths mutate it; two
    # concurrent groups of one fingerprint must not interleave)
    solver_lock: threading.Lock = dataclasses.field(
        default_factory=threading.Lock
    )
    # placement-resident template forms (serve/placement): the template
    # materialized on a routed device or replicated over a mesh, built
    # once per placement key by the active PlacementPolicy (which also
    # guards access with its own lock) and dropped on eviction
    placed: dict = dataclasses.field(
        default_factory=dict, repr=False, compare=False
    )


def template_signature(template) -> tuple:
    """Hashable (treedef, leaf shapes/dtypes) signature of a template
    pytree.  Two entries with equal signatures and equal config produce
    identical traces, so they may share one jitted executable — this is
    what makes a shape-bucket hit an XLA compile-cache hit."""
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(template)
    return (
        str(treedef),
        tuple(
            (tuple(l.shape), str(l.dtype))
            for l in leaves
            if hasattr(l, "shape")
        ),
    )


class HierarchyCache:
    """LRU cache: (padded fingerprint, config hash, dtype) -> entry.

    ``on_evict(key, entry)`` fires (outside the cache lock) for every
    LRU-evicted entry — the service uses it to drop the entry's
    orphaned AOT executables from the CompileCache, which otherwise
    leak until process exit."""

    def __init__(self, max_entries: int = 64,
                 metrics: Optional[ServeMetrics] = None,
                 on_evict: Optional[Callable] = None):
        self.max_entries = max_entries
        self.metrics = metrics or ServeMetrics()
        self.on_evict = on_evict
        self._lock = threading.Lock()
        self._entries: OrderedDict = OrderedDict()

    def __len__(self):
        return len(self._entries)

    def _notify_evict(self, evicted):
        """Run the eviction callback for popped (key, entry) pairs —
        after the cache lock is released (the callback takes other
        locks); callback failures never poison the insert path."""
        if self.on_evict is None:
            return
        for key, entry in evicted:
            try:
                self.on_evict(key, entry)
            except Exception:  # noqa: BLE001 — eviction housekeeping
                pass

    def any_with_signature(self, signature) -> bool:
        """Does any CACHED entry share this template signature?  Two
        entries with equal signatures share compiled executables, so
        eviction of one must not drop the other's programs."""
        with self._lock:
            return any(
                e.signature == signature
                for e in self._entries.values()
            )

    def insert(self, fingerprint: str, cfg_key: str, dtype,
               entry: HierarchyEntry):
        """Directly insert a pre-built entry (warm boot restore path):
        neither a hit nor a miss; LRU bounds still apply."""
        key = (fingerprint, cfg_key, str(dtype))
        evicted = []
        with self._lock:
            self._entries[key] = entry
            self._entries.move_to_end(key)
            while len(self._entries) > self.max_entries:
                evicted.append(self._entries.popitem(last=False))
                self.metrics.inc("cache_evictions")
        self._notify_evict(evicted)

    def peek(
        self, fingerprint: str, cfg_key: str, dtype
    ) -> Optional[HierarchyEntry]:
        """Cached entry or None — never builds.  Used by the flusher's
        quarantine path (reuse the pattern's hierarchy for isolated
        re-solves) and by submit-time compile warm-up."""
        key = (fingerprint, cfg_key, str(dtype))
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None:
                self._entries.move_to_end(key)
            return entry

    def get_or_build(
        self, pattern: PaddedPattern, cfg_key: str, dtype,
        build: Callable[[], HierarchyEntry],
    ) -> HierarchyEntry:
        key = (pattern.fingerprint, cfg_key, str(dtype))
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None:
                self._entries.move_to_end(key)
                self.metrics.inc("cache_hits")
                return entry
        # build outside the lock: setup is seconds-long and other
        # fingerprints must not queue behind it
        self.metrics.inc("cache_misses")
        self.metrics.inc("setups")
        entry = build()
        evicted = []
        with self._lock:
            self._entries[key] = entry
            self._entries.move_to_end(key)
            while len(self._entries) > self.max_entries:
                evicted.append(self._entries.popitem(last=False))
                self.metrics.inc("cache_evictions")
        self._notify_evict(evicted)
        return entry

    def bytes_by_dtype(self) -> dict:
        """Resident bytes of every cached hierarchy entry, summed per
        array dtype (``{"float32": n, "float64": m, "int32": k, ...}``)
        — the observability surface of the mixed-precision policy: a
        ``hierarchy_dtype=FLOAT32`` hierarchy's halved value bytes show
        up as mass moving from the float64 to the float32 family
        (``amgx_cache_hierarchy_bytes{dtype=...}``).  Leaves shared
        between the template solver's params and the batch template
        (object-identity aliasing, exactly what the store dedups on)
        count once."""
        import jax
        import numpy as np

        with self._lock:
            entries = list(self._entries.values())
        out: dict = {}
        seen: set = set()
        for e in entries:
            roots = [getattr(e.solver, "_params", None), e.template]
            for leaf in jax.tree_util.tree_leaves(roots):
                if not hasattr(leaf, "nbytes") or id(leaf) in seen:
                    continue
                seen.add(id(leaf))
                try:
                    key = str(np.dtype(leaf.dtype))
                except Exception:  # noqa: BLE001 — exotic leaf
                    key = "other"
                out[key] = out.get(key, 0) + int(leaf.nbytes)
        return out

    def bytes_by_format(self) -> dict:
        """Resident bytes of every cached hierarchy entry, summed per
        accel format (``{"MATRIX_FREE": n, "DIA": m, ...}``) — the
        observability surface of the matrix-free compression: a level
        whose DIA value planes collapsed to O(1) stencil coefficients
        shows up as mass moving from the DIA to the MATRIX_FREE family
        (``amgx_cache_hierarchy_bytes{format=...}``).  Arrays not owned
        by a SparseMatrix (vectors, smoother state) count as "other";
        aliased leaves count once, on the first format seen."""
        import jax

        from amgx_tpu.core.matrix import SparseMatrix

        with self._lock:
            entries = list(self._entries.values())
        out: dict = {}
        seen: set = set()

        def _fmt(m: SparseMatrix) -> str:
            if m.has_geo_transfer:
                return "GEO_TRANSFER"
            if m.has_matrix_free:
                return "MATRIX_FREE"
            if m.has_dia:
                return "DIA"
            if m.has_dense:
                return "DENSE"
            if m.has_ell:
                return "ELL"
            return "CSR"

        def _tally(leaf, fmt: str):
            if hasattr(leaf, "nbytes") and id(leaf) not in seen:
                seen.add(id(leaf))
                out[fmt] = out.get(fmt, 0) + int(leaf.nbytes)

        for e in entries:
            roots = [getattr(e.solver, "_params", None), e.template]
            mats = jax.tree_util.tree_leaves(
                roots, is_leaf=lambda x: isinstance(x, SparseMatrix)
            )
            for node in mats:
                if isinstance(node, SparseMatrix):
                    fmt = _fmt(node)
                    for leaf in jax.tree_util.tree_leaves(node):
                        _tally(leaf, fmt)
                else:
                    _tally(node, "other")
        return out

    def clear(self):
        with self._lock:
            self._entries.clear()


# process-wide compile worker: AOT warm-ups from every service share one
# background thread, so a cold bucket's compile never runs on a flush
# path or on the dispatch worker (head-of-line isolation), and idle
# services don't each pin a thread
_COMPILE_POOL: Optional[concurrent.futures.ThreadPoolExecutor] = None
_COMPILE_POOL_LOCK = threading.Lock()


def _compile_pool() -> concurrent.futures.ThreadPoolExecutor:
    global _COMPILE_POOL
    with _COMPILE_POOL_LOCK:
        if _COMPILE_POOL is None:
            _COMPILE_POOL = concurrent.futures.ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="serve-compile"
            )
        return _COMPILE_POOL


class CompileCache:
    """(template signature, batch bucket) -> compiled batched-solve
    executable.

    Two entries with equal signatures produce identical traces (the
    template is an ARGUMENT), so a bucket hit is an XLA compile-cache
    hit — same dedup contract as the old service-internal dict, plus:

    * **AOT compiles** (``jit(...).lower(...).compile()``) against
      ShapeDtypeStructs, so compilation needs no concrete batch and can
      run BEFORE the first flush of a bucket;
    * **background warm-up**: :meth:`warm` schedules the compile on a
      shared single-thread pool; a flush that arrives first blocks on
      the in-flight future instead of compiling again, and flushes of
      already-warm buckets never queue behind a cold compile;
    * **buffer donation**: the batched x0 is donated
      (``donate_argnums``) so XLA reuses its buffer for the solution
      output instead of allocating a fresh ``(B, n)`` array per flush.
      ``donate=None`` defers to the platform default
      (:func:`amgx_tpu.solvers.base.donation_enabled`: accelerators
      yes, CPU no — donation serializes CPU dispatch); True/False
      force it, e.g. for bitwise A/B tests.
    """

    def __init__(self, metrics: Optional[ServeMetrics] = None,
                 donate: Optional[bool] = None):
        self.metrics = metrics or ServeMetrics()
        self.donate = donate
        self._lock = threading.Lock()
        self._fns: dict = {}
        self._futures: dict = {}
        # signatures evicted while a warm-up was still compiling: the
        # finishing compile hands its result to waiters but must not
        # re-insert it (the executable would leak until process exit —
        # the orphan class evict_signature exists to close)
        self._dead_sigs: set = set()

    def __len__(self):
        return len(self._fns)

    def _donate(self) -> bool:
        if self.donate is not None:
            return bool(self.donate)
        from amgx_tpu.solvers.base import donation_enabled

        return donation_enabled()

    def _compile(self, entry: HierarchyEntry, Bb: int):
        import jax

        pat = entry.pattern
        dt = entry.solver.A.values.dtype
        jitted = jax.jit(
            entry.batch_fn,
            donate_argnums=(3,) if self._donate() else (),
        )
        try:
            return jitted.lower(
                entry.template,
                jax.ShapeDtypeStruct((Bb, pat.nnzb), dt),
                jax.ShapeDtypeStruct((Bb, pat.nb), dt),
                jax.ShapeDtypeStruct((Bb, pat.nb), dt),
            ).compile()
        except Exception as e:
            raise ResourceError(
                f"AOT compile failed (B={Bb}): {type(e).__name__}: {e}"
            ) from e

    def _resolve(self, key, entry: HierarchyEntry, Bb: int, fut):
        try:
            fn = self._compile(entry, Bb)
        except BaseException as e:  # propagate to every waiter
            with self._lock:
                self._futures.pop(key, None)
            fut.set_exception(e)
            raise
        with self._lock:
            self._futures.pop(key, None)
            if key[0] not in self._dead_sigs:
                self._fns[key] = fn
        self.metrics.inc("compiles")
        fut.set_result(fn)
        return fn

    def get(self, entry: HierarchyEntry, Bb: int):
        """Executable for (entry.signature, Bb): cached, or joined from
        an in-flight warm-up, or compiled inline on the CALLER (the
        flusher thread — never the dispatch worker)."""
        key = (entry.signature, Bb)
        with self._lock:
            self._dead_sigs.discard(key[0])  # signature is live again
            fn = self._fns.get(key)
            if fn is not None:
                self.metrics.inc("bucket_hits")
                return fn
            fut = self._futures.get(key)
            if fut is None:
                fut = concurrent.futures.Future()
                self._futures[key] = fut
                mine = True
            else:
                mine = False
        if mine:
            return self._resolve(key, entry, Bb, fut)
        return fut.result()

    def evict_signature(self, signature) -> int:
        """Drop every compiled executable of one template signature
        (the hierarchy cache evicted its last entry with it) and count
        them under ``compile_evictions``.  In-flight warm-up futures
        are left to finish — their waiters still need the result — but
        the signature is tombstoned so the finishing compile does not
        re-insert (and thereby leak) its executable; get/warm for the
        signature clear the tombstone."""
        if signature is None:
            return 0
        with self._lock:
            keys = [k for k in self._fns if k[0] == signature]
            for k in keys:
                del self._fns[k]
            if any(k[0] == signature for k in self._futures):
                self._dead_sigs.add(signature)
        if keys:
            self.metrics.inc("compile_evictions", len(keys))
        return len(keys)

    def warm(self, entry: HierarchyEntry, Bb: int):
        """Schedule a background AOT compile for (entry.signature, Bb)
        if neither an executable nor an in-flight compile exists."""
        key = (entry.signature, Bb)
        with self._lock:
            self._dead_sigs.discard(key[0])  # signature is live again
            if key in self._fns or key in self._futures:
                return
            fut = concurrent.futures.Future()
            self._futures[key] = fut
        self.metrics.inc("compile_warmups")

        def job():
            try:
                self._resolve(key, entry, Bb, fut)
            except BaseException:  # noqa: BLE001 — recorded on future
                pass

        _compile_pool().submit(job)
