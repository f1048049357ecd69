"""``GraphStore`` — one typed front door over every storage backend.

A store consumes the typed op IR (``OpBatch`` / ``ReadOp`` /
``AnalyticsOp``) and hides how state is laid out: ``LocalStore`` wraps the
eager single-shard ``RadixGraph``; ``ShardedStore`` wraps the
``dist.graph_engine`` factories (mesh and budgets captured at
construction, ``make_*`` closures built lazily and jit-cached per spec).
Both answer reads and analytics in the SAME backend-independent form, so
benchmarks, examples, the dryrun harness and the query service drive
either through one code path — and a new backend (multi-host epoch
handshake, another storage design, a CPU fallback) is a
``register_backend`` call, not a rewrite.

Epochs: ``capture()`` returns an O(1) immutable handle to the current
functional state (the paper's MVCC versioned arrays); every read/analytics
call accepts ``at=handle`` to answer against that version instead of the
live state. Sealing an epoch in the serving layer is just ``capture()``.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable, Dict, Optional, Protocol, runtime_checkable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import edgepool as ep
from repro.core import epoch_delta as ed
from repro.core import radixgraph as rg
from repro.core import vertex_table as vt_mod
from repro.core.keys import pack_keys, unpack_keys
from repro.core.radixgraph import RadixGraph, interleave_undirected
from repro.core.sort import SortSpec
from repro.core.sort_optimizer import optimize_sort
from repro.core.status import Reason
from repro.dist import graph_engine as ge

from .ir import (AnalyticsOp, AnalyticsResult, ApplyResult, OpBatch,
                 ReadOp, UnsupportedOpError)
from .registry import AnalyticsSpec, analytics_spec

__all__ = ["GraphStore", "Epoch", "LocalStore", "ShardedStore",
           "make_store", "register_backend", "available_backends"]


@dataclasses.dataclass(frozen=True)
class Epoch:
    """Immutable capture of a store's state. O(1): functional states are
    pytree references, so holding an Epoch IS retaining the MVCC version —
    drop the handle to free it. ``cache`` rides the handle (derived
    artifacts like the CSR snapshot), so freeing the handle frees them
    too — stores never pin a dropped epoch."""

    state: Any
    seq: int
    cache: dict = dataclasses.field(default_factory=dict, compare=False,
                                    repr=False)


@runtime_checkable
class GraphStore(Protocol):
    """The protocol every backend implements (structural — no base class
    to inherit; ``register_backend`` is the only ceremony)."""

    backend: str
    n_shards: int

    def apply(self, batch: OpBatch) -> ApplyResult: ...
    def read(self, op: ReadOp, at: Optional[Epoch] = None) -> Any: ...
    def analytics(self, op: AnalyticsOp,
                  at: Optional[Epoch] = None) -> Any: ...
    def capture(self) -> Epoch: ...
    def clock(self, at: Optional[Epoch] = None) -> int: ...


# jitted single-shard read programs shared by every LocalStore (static
# specs hash per-config, so distinct stores share compile caches the same
# way RadixGraph's module-level wrappers do)
_lookup = jax.jit(rg.step_lookup, static_argnums=(0, 1))
_degree = jax.jit(rg.step_degree_counts, static_argnums=(0, 1))
_neighbors = jax.jit(rg.step_neighbors, static_argnums=(0, 1, 4))
_snapshot = jax.jit(rg.step_snapshot, static_argnums=(0, 1, 2))


def _values_item(d: dict) -> dict:
    return {int(k): (v.item() if hasattr(v, "item") else v)
            for k, v in d.items()}


def _stale_gen(prev_handle: Optional[Epoch], at: Optional[Epoch],
               gen: int) -> bool:
    """True when either epoch handle predates the store's last
    ``restore()`` — ``capture`` stamps handles with the restore
    generation, so a warm chain can never silently span a restore (the
    restored lineage may reuse seqs, defrag counters and row offsets)."""
    return any(ep is not None and ep.cache.get("gen", 0) != gen
               for ep in (prev_handle, at))


class LocalStore:
    """Single-shard backend: the eager ``RadixGraph`` behind the IR.

    Constructor kwargs are ``RadixGraph``'s (plus ``m_cap`` — the CSR pad
    of snapshots/analytics; analytics cost scales with it, so benchmarks
    pass a tight bound). The wrapped graph stays reachable as ``.graph``
    for backend-specific extras (MVCC version labels, defrag, memory
    accounting).

    Each call records host spans (``jax.profiler.TraceAnnotation``, so
    only while a profiler trace runs), each with the call's request number
    ``req``: ``graph.apply`` (``.stage``, ``.dispatch``, ``.wait``, from
    ``RadixGraph``), ``graph.capture``, ``graph.read`` (``.dispatch``,
    ``.wait``, ``.fetch``) and ``graph.analytics`` (``.snapshot``,
    ``.run``, ``.result``)."""

    backend = "local"
    supported_ops = frozenset(("edges", "add_vertices", "delete_vertices"))

    def __init__(self, m_cap: Optional[int] = None,
                 max_delta_frac: float = 0.1, **graph_kwargs):
        self.graph = RadixGraph(**graph_kwargs)
        self.n_shards = 1
        self.m_cap = m_cap or self.graph.pool_spec.capacity_entries
        self.max_delta_frac = max_delta_frac
        self._seq = 0
        self._req = 0           # request number of the trace spans
        self._restore_gen = 0   # bumped by every restore(): epoch handles
        #                         captured before it are no longer delta-safe
        # host-clock counters: ``host_stage_ms`` staging + dispatch of the
        # flushes, ``flush_wait_ms`` the flushes' waits for their drop
        # counts; ``read_fetch_ms`` / ``read_fetch_bytes`` the reads'
        # device-to-host copies
        self.stats = dict(ops_applied=0, ops_dropped=0, defrags=0,
                          defrag_ms=0.0, defrag_host_ms=0.0,
                          defrag_sync_ms=0.0, tiles_scanned=0,
                          flushes=0, super_batches=0,
                          host_stage_ms=0.0, flush_wait_ms=0.0,
                          read_fetch_ms=0.0, read_fetch_bytes=0)

    def _request(self) -> int:
        self._req += 1
        return self._req

    # ---- mutation ----
    def apply(self, batch: OpBatch) -> ApplyResult:
        if len(batch) == 0:
            return ApplyResult(0, 0)
        req = self._request()
        with jax.profiler.TraceAnnotation("graph.apply", req=req):
            return self._apply(batch, req)

    def _apply(self, batch: OpBatch, req: int) -> ApplyResult:
        self._seq += 1
        g = self.graph
        if batch.kind == "edges":
            d0 = g.dropped_ops
            g.apply_ops(batch.src, batch.dst, batch.weight, req)
            res = ApplyResult(len(batch), g.dropped_ops - d0)
        else:
            o0 = int(g.state.vt.overflow)
            if batch.kind == "add_vertices":
                g.add_vertices(batch.ids)
            else:
                g.delete_vertices(batch.ids)
            res = ApplyResult(len(batch), int(g.state.vt.overflow) - o0)
        self.stats["ops_applied"] += res.applied
        self.stats["ops_dropped"] += res.dropped
        # maintenance counters ride every write result: the write path's
        # spike/scan accounting is a recorded artifact, not a debug log
        self.stats["defrags"] = g.num_defrags
        self.stats["defrag_ms"] = round(g.defrag_ms, 3)
        self.stats["defrag_host_ms"] = round(g.defrag_host_ms, 3)
        self.stats["defrag_sync_ms"] = round(g.defrag_sync_ms, 3)
        self.stats["tiles_scanned"] = g.tiles_scanned
        self.stats["flushes"] = g.pipe_flushes
        self.stats["super_batches"] = g.pipe_super_batches
        self.stats["host_stage_ms"] = round(g.pipe_stage_ms, 3)
        self.stats["flush_wait_ms"] = round(g.pipe_wait_ms, 3)
        return res

    # ---- epochs ----
    def capture(self) -> Epoch:
        with jax.profiler.TraceAnnotation("graph.capture",
                                          req=self._request()):
            # exempt the captured state from steady-state buffer donation
            self.graph.pin_live_state()
            return Epoch(self.graph.state, self._seq,
                         cache={"gen": self._restore_gen})

    def clock(self, at: Optional[Epoch] = None) -> int:
        state = at.state if at is not None else self.graph.state
        return int(state.pool.clock) - 1

    # ---- durability hooks (repro.storage) ----
    def durable_state(self):
        """The live functional state plus the HOST counters a restored
        process needs for deterministic resume (capture seq, drop
        accounting, the defrag watermark the spike attribution uses)."""
        return self.graph.state, dict(
            seq=self._seq, dropped_ops=self.graph.dropped_ops,
            seen_defrags=self.graph._seen_defrags,
            ops_applied=self.stats["ops_applied"],
            ops_dropped=self.stats["ops_dropped"])

    def load_durable_state(self, state, meta: dict):
        """Install a checkpointed state as the live image. Epoch handles
        captured BEFORE this call are lineage-divergent: ``capture`` tags
        handles with a restore generation and ``analytics_advance``
        refuses cross-generation windows (``Reason.RESTORE_BOUNDARY``)."""
        g = self.graph
        g.state = jax.tree.map(jnp.asarray, state)
        g._invalidate()
        g.pin_live_state()      # fresh host arrays must not be donated
        g.dropped_ops = int(meta.get("dropped_ops", 0))
        g._seen_defrags = int(meta.get(
            "seen_defrags", np.asarray(g.state.pool.defrags)))
        self._seq = int(meta.get("seq", 0))
        self.stats["ops_applied"] = int(meta.get("ops_applied", 0))
        self.stats["ops_dropped"] = int(meta.get("ops_dropped", 0))
        self._restore_gen += 1

    def checkpoint(self, directory, **kw):
        """Write an epoch-consistent checkpoint of the live state (full or
        incremental — see ``repro.storage.checkpoint``)."""
        from repro.storage.checkpoint import save_graph_checkpoint
        return save_graph_checkpoint(directory, self, **kw)

    def restore(self, directory, ckpt_id: Optional[int] = None):
        """Restore the live state from the latest (or given) valid
        checkpoint chain under ``directory``."""
        from repro.storage.checkpoint import restore_graph_checkpoint
        return restore_graph_checkpoint(directory, self, ckpt_id)

    def _state(self, at: Optional[Epoch]):
        return at.state if at is not None else self.graph.state

    # ---- reads ----
    def _per_key(self, state, ids, fn):
        out = []
        for keys, _ in self.graph._key_batches(ids):
            out.append(np.asarray(fn(state, keys)))
        n = len(np.asarray(ids))
        return (np.concatenate(out)[:n] if out
                else np.zeros((0,), np.int32))

    def _snap(self, at: Optional[Epoch]):
        if at is None:
            return self.graph.snapshot(m_cap=self.m_cap)    # epoch-cached
        # epoch-pinned reads share one snapshot per handle; it rides the
        # handle's cache, so dropping the Epoch frees it with the state
        snap = at.cache.get("snap")
        if snap is None:
            snap = at.cache["snap"] = _snapshot(
                self.graph.sort_spec, self.graph.pool_spec, self.m_cap,
                at.state)
        return snap

    def read(self, op: ReadOp, at: Optional[Epoch] = None):
        req = self._request()
        with jax.profiler.TraceAnnotation("graph.read", req=req):
            return self._read(op, self._state(at), at, req)

    def _fetch(self, tree, req: int):
        """Copy a read's device arrays to the host, inside the
        ``graph.read.fetch`` span, counted in ``read_fetch_ms`` and
        ``read_fetch_bytes``."""
        t = time.perf_counter()
        with jax.profiler.TraceAnnotation("graph.read.fetch", req=req):
            host = jax.tree.map(np.asarray, tree)
        self.stats["read_fetch_ms"] += (time.perf_counter() - t) * 1000.0
        self.stats["read_fetch_bytes"] += sum(
            a.nbytes for a in jax.tree.leaves(host))
        return host

    def _read(self, op: ReadOp, state, at: Optional[Epoch], req: int):
        g = self.graph
        if op.kind in ("lookup", "degree", "neighbors"):
            width = op.width or g.pool_spec.dmax
            with jax.profiler.TraceAnnotation("graph.read.dispatch", req=req):
                outs = []
                for keys, _ in g._key_batches(op.ids):
                    if op.kind == "neighbors":
                        bd, bw, _, bcnt = _neighbors(g.sort_spec, g.pool_spec,
                                                     state, keys, width, None)
                        outs.append((bd, bw, bcnt))
                    else:
                        fn = _lookup if op.kind == "lookup" else _degree
                        outs.append(fn(g.sort_spec, g.pool_spec, state, keys))
            with jax.profiler.TraceAnnotation("graph.read.wait", req=req):
                jax.block_until_ready(outs)
            n = len(np.asarray(op.ids))
            if op.kind != "neighbors":
                out = self._fetch(outs, req)
                off = (np.concatenate(out)[:n] if out
                       else np.zeros((0,), np.int32))
                return off >= 0 if op.kind == "lookup" else off
            outs, ids_np = self._fetch((outs, state.vt.ids), req)
            d, w, cnt = (np.concatenate(x)[:n] for x in zip(*outs))
            oc = np.clip(d, 0, ids_np.shape[0] - 1)
            gids = unpack_keys(ids_np[oc])
            return [(gids[i, :cnt[i]], w[i, :cnt[i]]) for i in range(n)]
        if op.kind == "num_vertices":
            if at is None:
                return self.graph.num_vertices
            return int(vt_mod.num_active(at.state.vt))
        if op.kind == "num_edges":
            if at is None:
                return self.graph.num_edges     # O(1) live counter
            return int(self._snap(at).m)
        if op.kind == "snapshot":
            return self._snap(at)
        raise ValueError(op.kind)

    # ---- analytics ----
    def _resolve_dyn(self, spec: AnalyticsSpec, state, params: dict):
        """Pop dyn params and resolve IDs -> row offsets. Returns
        ``(dyn, dyn_rows, absent_source)``; ``dyn_rows`` carries the host
        ints the advance phases take."""
        g = self.graph
        look = lambda s, k: _lookup(g.sort_spec, g.pool_spec, s, k)
        dyn, dyn_rows, absent_source = [], [], False
        for pname, kind in spec.dyn:
            v = params.pop(pname)
            if kind == "id":
                off = self._per_key(state, np.asarray([v], np.uint64),
                                    look)[0]
                if off < 0:
                    absent_source = True
                dyn_rows.append(max(int(off), 0))
                dyn.append(jnp.int32(max(int(off), 0)))
            else:
                ids = np.asarray(v, np.uint64)
                off = self._per_key(state, ids, look)
                if spec.result == "per_query":
                    dyn.append((jnp.asarray(np.clip(off, 0, None),
                                            jnp.int32), off))
                else:
                    # per-vertex source sets (BC): absent sources
                    # contribute nothing — drop them, like the mesh loop
                    dyn.append(jnp.asarray(off[off >= 0], jnp.int32))
        return dyn, dyn_rows, absent_source

    def _per_vertex_value(self, raw: np.ndarray, snap) -> dict:
        active = np.asarray(snap.active)
        vids = unpack_keys(np.asarray(snap.ids))
        # .tolist() yields Python scalars in one C pass — no per-vertex
        # .item() loop on the read path
        return dict(zip(vids[active].tolist(), raw[active].tolist()))

    def analytics(self, op: AnalyticsOp, at: Optional[Epoch] = None):
        return self.analytics_result(op, at).value

    def analytics_result(self, op: AnalyticsOp, at: Optional[Epoch] = None,
                         _reason: str = "") -> AnalyticsResult:
        """From-scratch run, answered as an ``AnalyticsResult`` whose
        ``raw`` per-row values seed a later ``analytics_advance``."""
        req = self._request()
        with jax.profiler.TraceAnnotation("graph.analytics", req=req):
            return self._analytics_result(op, at, _reason, req)

    def _analytics_result(self, op: AnalyticsOp, at: Optional[Epoch],
                          _reason: str, req: int) -> AnalyticsResult:
        spec = analytics_spec(op.name)
        state = self._state(at)
        with jax.profiler.TraceAnnotation("graph.analytics.snapshot",
                                          req=req):
            snap = self._snap(at)
        with jax.profiler.TraceAnnotation("graph.analytics.run", req=req):
            params = dict(op.params)
            dyn, _rows, absent_source = self._resolve_dyn(spec, state,
                                                          params)
            n_cap = snap.indptr.shape[0] - 1
            iters = 0
            if absent_source:
                vals = np.full((n_cap,), spec.absent)
            else:
                args = [a[0] if isinstance(a, tuple) else a for a in dyn]
                vals = spec.single(snap, *args, **params)
                if isinstance(vals, tuple):      # convergence entries: (v, it)
                    vals, it = vals
                    iters = int(np.asarray(it))
                jax.block_until_ready(vals)
        with jax.profiler.TraceAnnotation("graph.analytics.result", req=req):
            seq = at.seq if at is not None else self._seq
            if spec.result == "scalar":
                v = np.asarray(vals).item()
                return AnalyticsResult(v, seq, "scratch", iters, _reason, v,
                                       at)
            if spec.result == "per_query":
                out = np.asarray(vals).copy()
                for a in dyn:
                    if isinstance(a, tuple):
                        out[np.asarray(a[1]) < 0] = 0   # absent queries -> 0
                return AnalyticsResult(out, seq, "scratch", iters, _reason,
                                       None, at)
            if spec.canonical_single is not None:
                vals = spec.canonical_single(vals, snap)
            raw = np.asarray(vals)
            return AnalyticsResult(self._per_vertex_value(raw, snap), seq,
                                   "scratch", iters, _reason, raw, at)

    def _csr(self, at: Epoch) -> ed.HostCsr:
        h = at.cache.get("hcsr")
        if h is None:
            h = at.cache["hcsr"] = ed.host_csr(self._snap(at))
        return h

    def _delta(self, prev: Epoch, cur: Epoch):
        key = ("delta", prev.seq)
        hit = cur.cache.get(key)
        if hit is None:     # shared across every analytic chained E->E'
            hit = cur.cache[key] = ed.extract_delta(
                prev.state, cur.state, self._csr(prev), self._csr(cur))
        return hit

    def analytics_advance(self, op: AnalyticsOp, prev: AnalyticsResult,
                          at: Optional[Epoch]) -> AnalyticsResult:
        """Advance ``prev`` to epoch ``at`` over the delta, falling back
        to ``analytics_result`` (with the reason recorded) whenever the
        window or the algorithm refuses — callers always get the exact
        answer, ``mode`` just says how it was produced."""
        spec = analytics_spec(op.name)
        if at is None or prev is None:
            return self.analytics_result(op, at, _reason=Reason.NO_WARM)
        if _stale_gen(prev.handle, at, self._restore_gen):
            # a restore() replaced the lineage: equal seq / defrag
            # counters no longer imply equal states or row identity
            return self.analytics_result(op, at,
                                         _reason=Reason.RESTORE_BOUNDARY)
        if prev.epoch == at.seq:
            return prev
        if (spec.advance is None or spec.result == "per_query"
                or prev.handle is None or prev.raw is None):
            return self.analytics_result(op, at, _reason=Reason.NO_WARM)
        delta, reason = self._delta(prev.handle, at)
        if delta is None:
            return self.analytics_result(op, at, _reason=reason)
        if delta.n_changed > self.max_delta_frac * max(delta.m_cur, 1):
            return self.analytics_result(op, at,
                                         _reason=Reason.DELTA_TOO_LARGE)
        snap = self._snap(at)
        params = dict(op.params)
        _dyn, rows, absent = self._resolve_dyn(spec, at.state, params)
        if absent:
            return self.analytics_result(op, at,
                                         _reason=Reason.ABSENT_SOURCE)
        out = spec.advance(prev.raw, delta, self._csr(prev.handle),
                           self._csr(at), tuple(rows), params)
        if out is None:
            return self.analytics_result(op, at,
                                         _reason=Reason.ADVANCE_REFUSED)
        raw, iters = out
        if spec.result == "scalar":
            return AnalyticsResult(int(raw), at.seq, "incremental",
                                   int(iters), "", int(raw), at)
        raw = np.asarray(raw)
        return AnalyticsResult(self._per_vertex_value(raw, snap), at.seq,
                               "incremental", int(iters), "", raw, at)

    # ---- epoch retention (MVCC pins for warm chains) ----
    def pin_epoch(self, at: Epoch):
        """Register ``at`` in the graph's MVCC version set (label —
        derived from the capture seq — is private to the epoch chain)."""
        self.graph.retain_version(at.state, -(1 + at.seq))

    def release_epoch(self, at: Epoch):
        self.graph.release_version(-(1 + at.seq))

    @property
    def retained_epochs(self) -> int:
        return sum(1 for lab, _, _ in self.graph._versions if lab < 0)


class ShardedStore:
    """Mesh backend: vertex-space sharding over ``dist.graph_engine``.

    Mesh, specs and exchange budgets are captured at construction; every
    ``make_*`` closure is built LAZILY on first use and cached per static
    spec (`_fn`), so a store only compiles the programs its workload
    actually exercises. The write path keeps the live state vertex-SYNCED
    (incremental registration exchange, skipped entirely for batches that
    create no vertices) so any captured epoch is analytics-ready."""

    backend = "sharded"
    supported_ops = frozenset(("edges",))   # vertex CRUD: LocalStore only

    def __init__(self, n_shards: int = 1, *, n_per_shard: int = 8192,
                 expected_n: int = 4096, key_bits: int = 32,
                 pool_blocks: int = 16384, block_size: int = 16,
                 k_max: int = 128, dmax: int = 2048,
                 batch: int = 1024, query_batch: int = 256,
                 m_cap: Optional[int] = None, axis: str = "data",
                 undirected: bool = False, pack: bool = True,
                 capacity_factor: float = 1.0,
                 route_budget: Optional[int] = None,
                 frontier_budget: Optional[int] = None,
                 sync_incremental: bool = True,
                 sync_budget: Optional[int] = None,
                 sort_capacity_factor: Optional[float] = None,
                 pipeline_depth: int = 8,
                 donate_steady_state: bool = True,
                 fuse_scan: bool = False,
                 max_delta_frac: float = 0.1,
                 devices=None):
        from jax.sharding import AxisType
        assert batch % n_shards == 0 and query_batch % n_shards == 0, \
            "batch sizes must be divisible by the shard count"
        self.n_shards = n_shards
        self.n_per_shard = n_per_shard
        self.key_bits = key_bits
        self.batch = batch
        self.query_batch = query_batch
        self.axis = axis
        self.undirected = undirected
        self.pack = pack
        self.capacity_factor = capacity_factor
        self.route_budget = route_budget
        self.frontier_budget = frontier_budget
        self.sync_incremental = sync_incremental
        self.pipeline_depth = pipeline_depth
        self.donate_steady_state = donate_steady_state
        self.fuse_scan = fuse_scan
        self.mesh = jax.make_mesh(
            (n_shards,), (axis,),
            devices=(devices if devices is not None
                     else jax.devices()[:n_shards]),
            axis_types=(AxisType.Auto,))
        cfg = optimize_sort(expected_n, key_bits, 5)
        self.sspec = SortSpec.from_config(cfg, n_per_shard,
                                          sort_capacity_factor)
        self.pspec = ep.PoolSpec(n_blocks=pool_blocks,
                                 block_size=block_size,
                                 k_max=k_max, dmax=dmax)
        self.m_cap = m_cap or self.pspec.capacity_entries
        if sync_budget is None:
            # one write step creates at most 2 * batch rows globally
            sync_budget = min(n_per_shard, 2 * batch // n_shards + 64)
        self.sync_budget = sync_budget
        self._live_state = None  # materialized on first use (compile-only
        #                          consumers like dryrun never allocate it)
        self._fns: Dict[Any, Callable] = {}
        self._synced_rows = np.zeros((n_shards,), np.int32)
        self._seq = 0
        self._snap_cache = None        # (state-ref, per-shard snapshots)
        self._host_cache = None        # (state-ref, host id/row view)
        self._full_sync_cache = None   # (state-ref, synced-state) pair
        self._seen_defrags = 0
        self._pinned = None            # donation-exempt live state pytree
        self._restore_gen = 0          # see LocalStore._restore_gen
        self.max_delta_frac = max_delta_frac
        self._retained: Dict[int, Epoch] = {}   # pinned epoch chain
        self.stats = dict(ops_applied=0, ops_dropped=0,
                          sync_runs=0, sync_skips=0, defrags=0,
                          defrag_ms=0.0, defrag_host_ms=0.0,
                          defrag_sync_ms=0.0, tiles_scanned=0,
                          flushes=0, super_batches=0,
                          host_stage_ms=0.0, flush_wait_ms=0.0)
        self._req = 0           # request number of the trace spans

    @property
    def state(self):
        """The live sharded state pytree, allocated lazily: AOT-lowering
        consumers (``state_struct``/``*_program``) never pay for it."""
        if self._live_state is None:
            # built sharded over the mesh axis: each device allocates only
            # its own shards, never the whole stack on one device
            self._live_state = ge.make_sharded_state(
                self.sspec, self.pspec, self.n_shards, self.n_per_shard,
                self.mesh, self.axis)
        return self._live_state

    @state.setter
    def state(self, value):
        self._live_state = value

    # ---- lazily-built, spec-cached jitted programs ----
    def _fn(self, key, build) -> Callable:
        f = self._fns.get(key)
        if f is None:
            f = self._fns[key] = jax.jit(build())
        return f

    def apply_program(self, donate: bool = False,
                      depth: Optional[int] = None) -> Callable:
        """The jitted routed-apply program. ``depth=None`` is the per-batch
        (B, ...) entry; any int selects the K-batch pipelined entry taking
        stacked (K, B, ...) super-batches — ONE cached callable serves every
        K (jit retraces per distinct leading dim). ``donate=True`` donates
        the state pytree (steady-state buffers reuse the old pool image)."""
        def build():
            if depth is None:
                return ge.make_apply_edges(
                    self.sspec, self.pspec, self.mesh, self.axis,
                    pack=self.pack, capacity_factor=self.capacity_factor,
                    route_budget=self.route_budget)
            return ge.make_apply_edges_pipelined(
                self.sspec, self.pspec, self.mesh, self.axis,
                pack=self.pack, capacity_factor=self.capacity_factor,
                route_budget=self.route_budget)
        key = ("apply" if depth is None else "applyK",
               "donate" if donate else "plain")
        if key not in self._fns:
            f = build()
            self._fns[key] = jax.jit(f, donate_argnums=(0,)) if donate \
                else jax.jit(f)
        return self._fns[key]

    def analytics_program(self, name: str, **static) -> Callable:
        """The jitted mesh program of a registered algorithm (also the
        AOT-compile entry the dryrun harness lowers)."""
        spec = analytics_spec(name)
        if spec.make_dist is None:
            raise NotImplementedError(
                f"analytics op {name!r} has no mesh combine loop "
                f"registered (repro.api.registry) — run it on a "
                f"LocalStore, or register a distributed form")
        key = ("alg", name, tuple(sorted(static.items())))
        return self._fn(key, lambda: spec.make_dist(
            self.sspec, self.pspec, self.mesh, self.axis, self.m_cap,
            self.frontier_budget, **static))

    def warm_program(self, name: str, **static) -> Callable:
        """The jitted warm-advance mesh program (``make_dist_warm``):
        ``f(state, *dyn, prev_raw) -> (values, iters)``. Shares the
        ``("algw", ...)`` cache slot ``analytics_advance`` uses, and is
        the AOT entry ``dryrun_graph --mode analytics --incremental``
        lowers. Raises for algorithms with no warm form (or whose knobs
        disable it, e.g. fixed-iteration PageRank)."""
        spec = analytics_spec(name)
        if spec.make_dist_warm is None:
            raise NotImplementedError(
                f"analytics op {name!r} has no warm mesh program "
                f"registered (repro.api.registry)")
        key = ("algw", name, tuple(sorted(static.items())))
        f = self._fns.get(key)
        if f is None:
            built = spec.make_dist_warm(
                self.sspec, self.pspec, self.mesh, self.axis, self.m_cap,
                self.frontier_budget, **static)
            if built is None:
                raise NotImplementedError(
                    f"analytics op {name!r} refuses a warm program for "
                    f"{static!r} (path-dependent without a tolerance)")
            f = self._fns[key] = jax.jit(built)
        return f

    def state_struct(self):
        """Shape/dtype pytree of a fresh sharded state (AOT lowering)."""
        return jax.eval_shape(lambda: ge.make_sharded_state(
            self.sspec, self.pspec, self.n_shards, self.n_per_shard))

    # ---- mutation ----
    def _keys(self, ids) -> np.ndarray:
        return np.asarray(pack_keys(np.asarray(ids, np.uint64),
                                    self.key_bits))

    def apply(self, batch: OpBatch) -> ApplyResult:
        if batch.kind not in self.supported_ops:
            raise UnsupportedOpError(
                batch.kind, self.backend,
                "sharded vertex-only mutation batches are not routed yet: "
                "vertices materialize from edge endpoints (plus the owner "
                "registration sync); use LocalStore for vertex CRUD")
        if len(batch) == 0:
            return ApplyResult(0, 0)
        self._req += 1
        with jax.profiler.TraceAnnotation("graph.apply", req=self._req):
            return self._apply(batch, self._req)

    def _stage(self, batch: OpBatch) -> list:
        """The flush staged once: interleaved, padded, packed and put on
        the device as super-batches, each a list of flat (B, ...) batch
        tuples or, fused, one stacked (k, B, ...) tuple. The ragged tail
        ships at its true depth k' < K (whole-batch padding would advance
        the pool clock and break parity with the sequential path)."""
        src, dst, w = batch.src, batch.dst, batch.weight
        if self.undirected:
            src, dst, w = interleave_undirected(src, dst, w)
        sk, dk = self._keys(src), self._keys(dst)
        B = self.batch
        N = len(src)
        NB = (N + B - 1) // B
        K = max(1, int(self.pipeline_depth))
        psk = np.zeros((NB * B, 2), np.uint32)
        pdk = np.zeros((NB * B, 2), np.uint32)
        pw = np.zeros((NB * B,), np.float32)
        mask = np.zeros((NB * B,), bool)
        psk[:N], pdk[:N], pw[:N], mask[:N] = sk, dk, w, True
        groups = []
        i = 0
        while i < NB:
            k = min(K, NB - i)
            lo, hi = i * B, (i + k) * B
            if k > 1 and self.fuse_scan:
                groups.append((jnp.asarray(psk[lo:hi].reshape(k, B, 2)),
                               jnp.asarray(pdk[lo:hi].reshape(k, B, 2)),
                               jnp.asarray(pw[lo:hi].reshape(k, B)),
                               jnp.asarray(mask[lo:hi].reshape(k, B))))
            else:
                groups.append([(jnp.asarray(psk[a:a + B]),
                                jnp.asarray(pdk[a:a + B]),
                                jnp.asarray(pw[a:a + B]),
                                jnp.asarray(mask[a:a + B]))
                               for a in range(lo, hi, B)])
            i += k
        return groups

    def _apply(self, batch: OpBatch, req: int) -> ApplyResult:
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("graph.apply.stage", req=req):
            groups = self._stage(batch)
        # dispatch the super-batches ASYNCHRONOUSLY — no np.asarray() per
        # batch
        drops = []
        with jax.profiler.TraceAnnotation("graph.apply.dispatch", req=req):
            for xs in groups:
                if isinstance(xs, tuple):
                    # opt-in fused entry: k batches as ONE lax.scan program.
                    # (Slower than k flat donated dispatches on XLA CPU —
                    # the loop-carried pool scatters lose the in-place-update
                    # optimization — but it is the single-program artifact
                    # the dryrun lowers and the parity suite certifies.)
                    donate = self.donate_steady_state and \
                        (self.state is not self._pinned)
                    fn = self.apply_program(donate=donate,
                                            depth=xs[0].shape[0])
                    self.state, d = fn(self.state, *xs)
                    drops.append(d)             # device array — no sync here
                else:
                    # default steady state: k flat donated dispatches with
                    # no host sync between them (donation re-checked per
                    # dispatch; after the first, the state is a fresh jit
                    # output)
                    for x in xs:
                        donate = self.donate_steady_state and \
                            (self.state is not self._pinned)
                        fn = self.apply_program(donate=donate)
                        self.state, d = fn(self.state, *x)
                        drops.append(d)
                self.stats["super_batches"] += 1
        self.stats["host_stage_ms"] = round(
            self.stats["host_stage_ms"] +
            (time.perf_counter() - t0) * 1000.0, 3)
        # ONE host sync per flush: the drop fetch forces the dispatched
        # chain; the defrag counter delta then attributes any rebuild
        # spike to this flush window instead of serializing every batch
        t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation("graph.apply.wait", req=req):
            dropped = int(sum(int(np.asarray(d).sum()) for d in drops))
            dsum = int(np.asarray(self.state.pool.defrags).sum())
        if dsum != self._seen_defrags:            # some shard rebuilt
            now = time.perf_counter()
            self.stats["defrag_ms"] = round(
                self.stats["defrag_ms"] + (now - t0) * 1000.0, 3)
            # split: staged/dispatched up to t1, device-blocked after
            self.stats["defrag_host_ms"] = round(
                self.stats["defrag_host_ms"] + (t1 - t0) * 1000.0, 3)
            self.stats["defrag_sync_ms"] = round(
                self.stats["defrag_sync_ms"] + (now - t1) * 1000.0, 3)
            self._seen_defrags = dsum
        self.stats["flush_wait_ms"] = round(
            self.stats["flush_wait_ms"] +
            (time.perf_counter() - t1) * 1000.0, 3)
        self.stats["flushes"] += 1
        self._seq += 1
        self._snap_cache = self._host_cache = None
        # raw submitted ops (undirected doubling is an internal detail),
        # so accounting matches ApplyResult and the local backend
        self.stats["ops_applied"] += len(batch)
        self.stats["ops_dropped"] += dropped
        self.stats["defrags"] = self._seen_defrags
        self.stats["tiles_scanned"] = int(
            np.asarray(self.state.pool.tiles_scanned).sum())
        if self.sync_incremental:
            self._maybe_sync_live()
        return ApplyResult(len(batch), dropped)

    def _maybe_sync_live(self):
        """Eager incremental vertex sync after a write batch: only rows
        created since the last sync are registered at their owner shards
        (compacted exchange w/ dense fallback); a batch creating no
        vertices skips the collective entirely."""
        rows = np.asarray(self.state.vt.num_rows)
        if np.array_equal(rows, self._synced_rows):
            self.stats["sync_skips"] += 1
            return
        fn = self._fn(("sync_inc",), lambda: ge.make_sync_vertices(
            self.sspec, self.pspec, self.mesh, self.axis,
            budget=self.sync_budget, incremental=True))
        self.state = fn(self.state, jnp.asarray(self._synced_rows))
        # np.array COPIES: np.asarray on CPU is a zero-copy view of the
        # live buffer, which the next (donating) apply would invalidate
        self._synced_rows = np.array(self.state.vt.num_rows)
        self.stats["sync_runs"] += 1

    # ---- epochs ----
    def capture(self) -> Epoch:
        # the handle retains the live arrays: exempt this state from
        # steady-state buffer donation (the next apply's first dispatch
        # runs the non-donating program, later ones donate fresh outputs)
        self._pinned = self.state
        return Epoch(self.state, self._seq,
                     cache={"gen": self._restore_gen})

    def clock(self, at: Optional[Epoch] = None) -> int:
        state = at.state if at is not None else self.state
        return int(np.asarray(state.pool.clock)[0]) - 1

    # ---- durability hooks (repro.storage) ----
    def durable_state(self):
        """Shard-stacked live state plus the host counters a restored
        process resumes ingest with (capture seq, incremental-sync
        watermark, defrag watermark)."""
        return self.state, dict(
            seq=self._seq, seen_defrags=self._seen_defrags,
            synced_rows=np.asarray(self._synced_rows).tolist(),
            ops_applied=self.stats["ops_applied"],
            ops_dropped=self.stats["ops_dropped"])

    def load_durable_state(self, state, meta: dict):
        """Install a checkpointed state as the live sharded image — every
        leaf is re-placed with the live template's sharding, so a restore
        works on a fresh store of the same spec in a new process."""
        from jax.sharding import NamedSharding
        from jax.sharding import PartitionSpec as P
        # the leading dim of every leaf is the shard dim: place it over
        # the mesh axis explicitly
        sharding = NamedSharding(self.mesh, P(self.axis))
        self._live_state = jax.tree.map(
            lambda a: jax.device_put(jnp.asarray(a), sharding), state)
        self._pinned = self._live_state   # aliased/fresh: never donate
        self._snap_cache = self._host_cache = self._full_sync_cache = None
        self._synced_rows = np.asarray(
            meta["synced_rows"], np.int32).copy() if "synced_rows" in meta \
            else np.array(self.state.vt.num_rows)
        self._seq = int(meta.get("seq", 0))
        self._seen_defrags = int(meta.get("seen_defrags", np.asarray(
            self.state.pool.defrags).sum()))
        self.stats["ops_applied"] = int(meta.get("ops_applied", 0))
        self.stats["ops_dropped"] = int(meta.get("ops_dropped", 0))
        self.stats["defrags"] = self._seen_defrags
        self._restore_gen += 1

    def checkpoint(self, directory, **kw):
        """Epoch-consistent checkpoint of the live sharded state."""
        from repro.storage.checkpoint import save_graph_checkpoint
        return save_graph_checkpoint(directory, self, **kw)

    def restore(self, directory, ckpt_id: Optional[int] = None):
        from repro.storage.checkpoint import restore_graph_checkpoint
        return restore_graph_checkpoint(directory, self, ckpt_id)

    def _state(self, at: Optional[Epoch]):
        return at.state if at is not None else self.state

    def _synced(self, state):
        """A vertex-synced view of ``state`` (identity when the write path
        keeps the live state registered as it goes)."""
        if self.sync_incremental:
            return state
        if self._full_sync_cache is not None and \
                self._full_sync_cache[0] is state:
            return self._full_sync_cache[1]
        fn = self._fn(("sync",), lambda: ge.make_sync_vertices(
            self.sspec, self.pspec, self.mesh, self.axis))
        synced = fn(state)
        self.stats["sync_runs"] += 1
        self._full_sync_cache = (state, synced)
        return synced

    # ---- reads ----
    def _snapshots(self, state):
        if self._snap_cache is not None and self._snap_cache[0] is state:
            return self._snap_cache[1]
        fn = self._fn(("snapshot",), lambda: ge.make_snapshot(
            self.sspec, self.pspec, self.mesh, self.axis, self.m_cap))
        snaps = fn(state)
        self._snap_cache = (state, snaps)
        return snaps

    def _host_view(self, state):
        """Host-side id/row maps of a (synced) state for lookup/neighbors:
        one device pull per state identity, then O(1) per query."""
        if self._host_cache is not None and self._host_cache[0] is state:
            return self._host_cache[1]
        ids = np.asarray(state.vt.ids)
        live = np.asarray(state.vt.del_time) == 0
        vid = unpack_keys(ids)
        owner = np.asarray(ge.shard_of_keys(
            jnp.asarray(ids.reshape(-1, 2)), self.n_shards)).reshape(
                ids.shape[:2])
        row_of = []
        present = set()
        for s in range(self.n_shards):
            rows = np.nonzero(live[s])[0]
            row_of.append(dict(zip(vid[s][rows].tolist(), rows.tolist())))
            present.update(row_of[-1])
        view = dict(vid=vid, live=live, owner=owner, row_of=row_of,
                    present=present)
        self._host_cache = (state, view)
        return view

    def read(self, op: ReadOp, at: Optional[Epoch] = None):
        state = self._state(at)
        if op.kind == "degree":
            fn = self._fn(("degree",), lambda: ge.make_khop_counts(
                self.sspec, self.pspec, self.mesh, self.axis))
            Q = self.query_batch
            keys = self._keys(op.ids)
            out = np.zeros((len(op.ids),), np.int32)
            for lo in range(0, len(op.ids), Q):
                chunk = keys[lo:lo + Q]
                buf = np.zeros((Q, 2), np.uint32)
                buf[:len(chunk)] = chunk
                cnt = np.asarray(fn(state, jnp.asarray(buf)))
                out[lo:lo + len(chunk)] = cnt[:len(chunk)]
            return out
        if op.kind == "lookup":
            present = self._host_view(self._synced(state))["present"]
            return np.array([int(x) in present for x in op.ids], bool)
        if op.kind == "neighbors":
            # edges live in the SOURCE's hash-owner shard: read that
            # shard's CSR row (host-materialized per-shard snapshots)
            view = self._host_view(state)
            snaps = self._snapshots(state)
            indptr = np.asarray(snaps.indptr)
            dst = np.asarray(snaps.dst)
            wgt = np.asarray(snaps.weight)
            out = []
            for x in np.asarray(op.ids, np.uint64):
                key = self._keys(np.asarray([x], np.uint64))
                s = int(np.asarray(ge.shard_of_keys(
                    jnp.asarray(key), self.n_shards))[0])
                row = view["row_of"][s].get(int(x))
                if row is None:
                    out.append((np.zeros((0,), np.uint64),
                                np.zeros((0,), np.float32)))
                    continue
                lo, hi = int(indptr[s][row]), int(indptr[s][row + 1])
                offs = dst[s][lo:hi]
                out.append((view["vid"][s][offs], wgt[s][lo:hi]))
            return out
        if op.kind == "num_vertices":
            view = self._host_view(self._synced(state))
            mine = view["live"] & (view["owner"] ==
                                   np.arange(self.n_shards)[:, None])
            return int(np.sum(mine))
        if op.kind == "num_edges":
            return int(np.asarray(self._snapshots(state).m).sum())
        if op.kind == "snapshot":
            return self._snapshots(state)
        raise ValueError(op.kind)

    # ---- analytics ----
    def _resolve_dyn(self, spec: AnalyticsSpec, params: dict):
        """Pop dyn params and resolve IDs -> packed mesh keys. Returns
        ``(dyn, query_ids)``."""
        dyn, query_ids = [], None
        for pname, kind in spec.dyn:
            v = params.pop(pname)
            if kind == "id":
                dyn.append(jnp.asarray(
                    self._keys(np.asarray([v], np.uint64))[0]))
            elif spec.result == "per_query":
                query_ids = np.asarray(v, np.uint64)
            else:
                # replicated source sets (BC): pad to the next power of
                # two with absent-key sentinels (hash to nothing, roff<0,
                # contribute zero) so distinct set sizes reuse a bounded
                # family of compiled programs
                ids = np.asarray(v, np.uint64)
                S = max(len(ids), 1)
                Sp = 1 << (S - 1).bit_length()
                buf = np.full((Sp, 2), 0xFFFFFFFF, np.uint32)
                buf[:len(ids)] = self._keys(ids)
                dyn.append(jnp.asarray(buf))
        return dyn, query_ids

    def analytics(self, op: AnalyticsOp, at: Optional[Epoch] = None):
        return self.analytics_result(op, at).value

    def analytics_result(self, op: AnalyticsOp, at: Optional[Epoch] = None,
                         _reason: str = "") -> AnalyticsResult:
        """From-scratch mesh run as an ``AnalyticsResult``; ``raw`` keeps
        the per-shard ``(n_shards, n_cap)`` values (scalar results: the
        per-shard partials) a later ``analytics_advance`` seeds from."""
        spec = analytics_spec(op.name)
        if op.name == "wcc" and self.key_bits > 32:
            raise NotImplementedError(
                "distributed WCC labels are single uint32 words (min "
                "vertex ID): key_bits > 32 needs a two-word label loop")
        params = dict(op.params)
        dyn, query_ids = self._resolve_dyn(spec, params)
        fn = self.analytics_program(op.name, **params)
        state = self._synced(self._state(at))
        seq = at.seq if at is not None else self._seq
        if query_ids is not None:
            # query batches ride the shard partition in fixed
            # ``query_batch`` chunks (ONE compiled shape, like the degree
            # read path); sentinel-padded tails answer 0 and are sliced
            Q = self.query_batch
            q = len(query_ids)
            keys = self._keys(query_ids)
            out = np.zeros((q,), np.int32)
            for lo in range(0, q, Q):
                n_c = min(Q, q - lo)
                buf = np.full((Q, 2), 0xFFFFFFFF, np.uint32)
                buf[:n_c] = keys[lo:lo + n_c]
                vals = np.asarray(fn(state, jnp.asarray(buf), *dyn))
                out[lo:lo + n_c] = vals[:n_c]
            return AnalyticsResult(out, seq, "scratch", 0, _reason,
                                   None, at)
        vals = fn(state, *dyn)
        iters = 0
        if isinstance(vals, tuple):         # convergence entries: (v, it)
            vals, it = vals
            iters = int(np.asarray(it).max())
        raw = np.asarray(vals)
        if spec.result == "scalar":
            return AnalyticsResult(int(raw.sum()), seq, "scratch", iters,
                                   _reason, raw, at)
        value = _values_item(
            ge.collect_owner_values(state, raw, self.n_shards))
        return AnalyticsResult(value, seq, "scratch", iters, _reason,
                               raw, at)

    def _csrs(self, at: Epoch):
        """Per-shard host CSR views of an epoch, cached on the handle."""
        h = at.cache.get("hcsr")
        if h is None:
            fn = self._fn(("snapshot",), lambda: ge.make_snapshot(
                self.sspec, self.pspec, self.mesh, self.axis, self.m_cap))
            snaps = fn(at.state)
            indptr = np.asarray(snaps.indptr)
            dst = np.asarray(snaps.dst)
            w = np.asarray(snaps.weight)
            act = np.asarray(snaps.active)
            ids = np.asarray(snaps.ids)
            m = np.asarray(snaps.m)
            h = at.cache["hcsr"] = [
                ed.HostCsr(indptr=indptr[s], dst=dst[s], weight=w[s],
                           active=act[s], ids=ids[s], m=int(m[s]))
                for s in range(self.n_shards)]
        return h

    def _delta(self, prev: Epoch, cur: Epoch):
        key = ("delta", prev.seq)
        hit = cur.cache.get(key)
        if hit is None:     # shared across every analytic chained E->E'
            hit = cur.cache[key] = ed.extract_delta_sharded(
                prev.state, cur.state, self._csrs(prev), self._csrs(cur))
        return hit

    def analytics_advance(self, op: AnalyticsOp, prev: AnalyticsResult,
                          at: Optional[Epoch]) -> AnalyticsResult:
        """Advance ``prev`` to epoch ``at``: warm mesh program when the
        registry has one (``make_dist_warm``), per-shard host advance
        otherwise (degree/num_edges — shard-local by the edge-placement
        invariant); any refusal falls back to scratch with the reason."""
        spec = analytics_spec(op.name)
        if at is None or prev is None:
            return self.analytics_result(op, at, _reason=Reason.NO_WARM)
        if _stale_gen(prev.handle, at, self._restore_gen):
            return self.analytics_result(op, at,
                                         _reason=Reason.RESTORE_BOUNDARY)
        if prev.epoch == at.seq:
            return prev
        if (spec.result == "per_query" or prev.handle is None
                or prev.raw is None or not self.sync_incremental
                or (spec.make_dist_warm is None and spec.advance is None)):
            return self.analytics_result(op, at, _reason=Reason.NO_WARM)
        deltas, reason = self._delta(prev.handle, at)
        if deltas is None:
            return self.analytics_result(op, at, _reason=reason)
        flags = ed.merged_flags(deltas)
        if flags["n_changed"] > self.max_delta_frac * \
                max(flags["m_cur"], 1):
            return self.analytics_result(op, at,
                                         _reason=Reason.DELTA_TOO_LARGE)
        if spec.warm_guard is not None:
            why = spec.warm_guard(flags)
            if why:
                return self.analytics_result(op, at, _reason=why)
        params = dict(op.params)
        dyn, _q = self._resolve_dyn(spec, params)
        if spec.make_dist_warm is not None:
            key = ("algw", op.name, tuple(sorted(params.items())))
            if key not in self._fns:
                f = spec.make_dist_warm(
                    self.sspec, self.pspec, self.mesh, self.axis,
                    self.m_cap, self.frontier_budget, **params)
                if f is None:       # e.g. fixed-iteration PageRank
                    return self.analytics_result(
                        op, at, _reason=Reason.NO_WARM_PROGRAM)
                self._fns[key] = jax.jit(f)
            fn = self._fns[key]
            vals, it = fn(at.state, *dyn, jnp.asarray(prev.raw))
            iters = int(np.asarray(it).max())
            raw = np.asarray(vals)
        else:
            pcsrs, ccsrs = self._csrs(prev.handle), self._csrs(at)
            raws, iters = [], 0
            for s in range(self.n_shards):
                o = spec.advance(prev.raw[s], deltas[s], pcsrs[s],
                                 ccsrs[s], (), params)
                if o is None:
                    return self.analytics_result(
                        op, at, _reason=Reason.ADVANCE_REFUSED)
                r, its = o
                raws.append(r)
                iters = max(iters, int(its))
            raw = np.asarray(raws) if spec.result == "scalar" \
                else np.stack(raws)
        if spec.result == "scalar":
            return AnalyticsResult(int(np.asarray(raw).sum()), at.seq,
                                   "incremental", iters, "", raw, at)
        value = _values_item(
            ge.collect_owner_values(at.state, raw, self.n_shards))
        return AnalyticsResult(value, at.seq, "incremental", iters, "",
                               raw, at)

    # ---- epoch retention (warm-chain pins) ----
    def pin_epoch(self, at: Epoch):
        self._retained[at.seq] = at

    def release_epoch(self, at: Epoch):
        self._retained.pop(at.seq, None)

    @property
    def retained_epochs(self) -> int:
        return len(self._retained)


# ---- backend registry ----

_BACKENDS: Dict[str, Callable[..., GraphStore]] = {}


def register_backend(name: str, factory: Callable[..., GraphStore]):
    """Register a GraphStore backend under ``name`` (see ``make_store``)."""
    _BACKENDS[name] = factory
    return factory


def available_backends():
    return sorted(_BACKENDS)


def make_store(backend: str, **kwargs) -> GraphStore:
    """Construct a registered backend: ``make_store('local', n_max=...)``
    or ``make_store('sharded', n_shards=...)``."""
    if backend not in _BACKENDS:
        raise KeyError(f"unknown GraphStore backend {backend!r}; "
                       f"registered: {available_backends()}")
    return _BACKENDS[backend](**kwargs)


register_backend("local", LocalStore)
register_backend("sharded", ShardedStore)
