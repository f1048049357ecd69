"""RadixGraph — the paper's full structure behind an ID-level API.

Host-side wrapper owning a ``GraphState`` pytree plus jitted, padded-batch
update/read functions. All device work is pure; every mutation returns a new
state, and retained old states are exactly the paper's MVCC versioned arrays.
"""
from __future__ import annotations

import functools
import math
import time
from dataclasses import dataclass
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from . import edgepool as ep
from . import sort as sort_mod
from . import vertex_table as vt_mod
from .keys import pack_keys, unpack_keys
from .sort import SortSpec, SortState
from .sort_optimizer import SortConfig, optimize_sort
from .vertex_table import VertexTable

__all__ = ["RadixGraph", "GraphState", "GraphSnapshot", "step_add_vertices",
           "step_delete_vertices", "step_update_edges",
           "step_update_edges_pipelined", "step_lookup",
           "step_degree_counts", "step_neighbors", "step_snapshot",
           "interleave_undirected"]


def interleave_undirected(src, dst, w):
    """Undirected edge-op doubling, shared by every storage backend:
    interleave the two directions so the mixed-op stream order is preserved
    (op i's orientations land at timestamps 2i, 2i+1)."""
    s2 = np.empty(2 * len(src), np.uint64)
    d2 = np.empty_like(s2)
    w2 = np.empty(2 * len(src), np.float32)
    s2[0::2], s2[1::2] = src, dst
    d2[0::2], d2[1::2] = dst, src
    w2[0::2], w2[1::2] = w, w
    return s2, d2, w2


class GraphState(NamedTuple):
    sort: SortState
    vt: VertexTable
    pool: ep.EdgePool


class GraphSnapshot(NamedTuple):
    """CSR view of the live graph (analytics input). Padded to m_cap."""

    indptr: jnp.ndarray   # int32[n_cap + 1]
    dst: jnp.ndarray      # int32[m_cap] destination offsets
    weight: jnp.ndarray   # float32[m_cap]
    n_rows: jnp.ndarray   # int32 — vertex-table high-water mark
    m: jnp.ndarray        # int32 — live edge count
    active: jnp.ndarray   # bool[n_cap] — row is a live vertex
    ids: jnp.ndarray      # uint32[n_cap, 2] — row -> vertex ID


# --------------------------------------------------------------------------
# pure per-shard state transitions
#
# These are the single-shard building blocks: plain functions of
# (static specs, GraphState, batched ops) -> new GraphState. The host
# ``RadixGraph`` wrapper jits them below; ``repro.dist.graph_engine``
# shard_maps/vmaps the very same functions over a stacked shard dim, so the
# single- and multi-shard paths share one implementation.
# --------------------------------------------------------------------------

def step_add_vertices(sspec: SortSpec, pspec: ep.PoolSpec, state: GraphState,
                      keys, mask):
    """Locate-or-insert vertices. Returns (state, offsets, created)."""
    st, vt, off, created = vt_mod.ensure_vertices(sspec, state.sort, state.vt,
                                                  keys, mask)
    return GraphState(st, vt, state.pool), off, created


def step_delete_vertices(sspec: SortSpec, pspec: ep.PoolSpec,
                         state: GraphState, keys, mask):
    """Mark vertices deleted at the current clock. Returns
    (state, offsets, found)."""
    ts = state.pool.clock
    st, vt, off, found = vt_mod.delete_vertices(sspec, state.sort, state.vt,
                                                keys, mask, ts)
    # a vertex delete hides every incident edge (in- AND out-) at read time;
    # in-degrees are not tracked, so the live-edge counter goes stale until
    # the next defrag / host recount resynchronizes it
    any_del = (jnp.sum(found.astype(jnp.int32)) > 0).astype(jnp.int32)
    pool = state.pool._replace(
        clock=state.pool.clock + 1,
        live_dirty=jnp.maximum(state.pool.live_dirty, any_del))
    return GraphState(st, vt, pool), off, found


def step_update_edges(sspec: SortSpec, pspec: ep.PoolSpec, state: GraphState,
                      src_keys, dst_keys, w, mask):
    """Apply a batch of edge ops by vertex KEY (``w == 0`` deletes).

    Returns (state, dropped): ``dropped`` counts masked ops that could not be
    applied — vertex-table exhaustion (either endpoint) or pool exhaustion.
    Every op runs under one named scope: ``vertex_index`` (SORT
    locate-or-insert of both endpoints), then ``apply_edge_updates``'
    ``group``, ``compact``, ``probe`` and ``append``.
    """
    B = src_keys.shape[0]
    with jax.named_scope("vertex_index"):
        keys = jnp.concatenate([src_keys, dst_keys], axis=0)
        m2 = jnp.concatenate([mask, mask])
        st, vt, off, _ = vt_mod.ensure_vertices(sspec, state.sort, state.vt,
                                                keys, m2)
        u, v = off[:B], off[B:]
        vtx_dropped = jnp.sum((mask & ((u < 0) | (v < 0))).astype(jnp.int32))
    pool, vt, dropped = ep.apply_edge_updates(pspec, state.pool, vt, u, v, w,
                                              mask)
    with jax.named_scope("append"):
        return GraphState(st, vt, pool), dropped + vtx_dropped


def step_update_edges_pipelined(sspec: SortSpec, pspec: ep.PoolSpec,
                                state: GraphState, src_keys, dst_keys, w,
                                mask):
    """Apply a STACKED (K, B, ...) super-batch of edge ops as one device
    program: a ``lax.scan`` of ``step_update_edges``, so K batches cost a
    single dispatch and the drop counter accumulates on device (one host
    fetch per flush instead of per batch).

    Bit-exact vs K sequential ``step_update_edges`` calls — the scan body IS
    the per-batch transition, overflow-defrag fallback (``lax.cond`` inside
    ``apply_edge_updates``) included, so a mid-super-batch rebuild behaves
    identically. Returns (state, dropped) with scalar summed drops.
    """
    def body(g, xs):
        return step_update_edges(sspec, pspec, g, *xs)

    state, drops = jax.lax.scan(body, state, (src_keys, dst_keys, w, mask))
    return state, jnp.sum(drops, dtype=jnp.int32)


def step_lookup(sspec: SortSpec, pspec: ep.PoolSpec, state: GraphState, keys):
    """Key -> vertex-table offset (-1 absent)."""
    return sort_mod.lookup(sspec, state.sort, keys)


def step_degree_counts(sspec: SortSpec, pspec: ep.PoolSpec, state: GraphState,
                       keys, read_ts=None):
    """Live (deduplicated, tombstone-free) out-degree per query key; 0 for
    absent vertices. The owner-side answer of the distributed 1-hop query."""
    with jax.named_scope("vertex_index"):
        off = sort_mod.lookup(sspec, state.sort, keys)
    _, _, _, cnt = ep.get_neighbors(pspec, state.pool, state.vt, off,
                                    read_ts=read_ts)
    return cnt


def step_neighbors(sspec: SortSpec, pspec: ep.PoolSpec, state: GraphState,
                   keys, width: int, read_ts=None):
    """Fused key->offset lookup + MVCC get-neighbors: one device dispatch per
    padded query batch (no host round-trip between SORT and the pool scan).
    Returns (dst_offsets, weights, ts, counts) with rows front-packed.
    Named scopes: ``vertex_index`` (the lookup), then ``get_neighbors``'
    ``gather`` and ``compact``."""
    with jax.named_scope("vertex_index"):
        off = sort_mod.lookup(sspec, state.sort, keys)
    return ep.get_neighbors(pspec, state.pool, state.vt, off,
                            read_ts=read_ts, width=width)


# --------------------------------------------------------------------------
# jitted host-API wrappers (static: sort spec, pool spec)
# --------------------------------------------------------------------------

_add_vertices = jax.jit(step_add_vertices, static_argnums=(0, 1))
_delete_vertices = jax.jit(step_delete_vertices, static_argnums=(0, 1))
_update_edges = jax.jit(step_update_edges, static_argnums=(0, 1))
# steady-state variants donate the input state pytree: XLA reuses the pool /
# vertex-table buffers for the output instead of allocating a second image
# (the pinned-state check in ``_apply_edge_batches`` keeps captured epochs
# and MVCC versions donation-exempt)
_update_edges_donate = jax.jit(step_update_edges, static_argnums=(0, 1),
                               donate_argnums=(2,))
_update_edges_pipe = jax.jit(step_update_edges_pipelined,
                             static_argnums=(0, 1))
_update_edges_pipe_donate = jax.jit(step_update_edges_pipelined,
                                    static_argnums=(0, 1), donate_argnums=(2,))
_lookup = jax.jit(step_lookup, static_argnums=(0, 1))
_neighbors = jax.jit(step_neighbors, static_argnums=(0, 1, 4))


def step_snapshot(sspec: SortSpec, pspec: ep.PoolSpec, m_cap: int,
                  state: GraphState, read_ts=None):
    """Build the CSR ``GraphSnapshot`` of the live (or ``read_ts``-versioned)
    graph. Pure per-shard transition: the host wrapper jits it below and the
    distributed engine shard_maps it per shard (``dist.graph_engine``).
    Named scopes: ``live_edges`` (the sort of the whole pool), ``csr``."""
    vt = state.vt
    n_cap = vt.size.shape[0]
    with jax.named_scope("live_edges"):
        so, sd, sw, stv, keep = ep.live_edges(pspec, state.pool, vt,
                                              read_ts=read_ts)
    with jax.named_scope("csr"):
        m = jnp.sum(keep.astype(jnp.int32))
        counts = jnp.zeros((n_cap,), jnp.int32).at[
            jnp.where(keep, so, n_cap)].add(1, mode="drop")
        indptr = jnp.concatenate([jnp.zeros((1,), jnp.int32),
                                  jnp.cumsum(counts)])
        # entries already sorted by (owner, dst); pack keeps to the front
        kpos = jnp.cumsum(keep.astype(jnp.int32)) - 1
        tgt = jnp.where(keep, kpos, m_cap)
        dst = jnp.full((m_cap,), -1, jnp.int32).at[tgt].set(sd, mode="drop")
        wgt = jnp.zeros((m_cap,), jnp.float32).at[tgt].set(sw, mode="drop")
        active = vt.del_time == 0
    return GraphSnapshot(indptr=indptr, dst=dst, weight=wgt,
                         n_rows=vt.num_rows, m=m, active=active, ids=vt.ids)


_snapshot = jax.jit(step_snapshot, static_argnums=(0, 1, 2))


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _fresh_state(sspec: SortSpec, pspec: ep.PoolSpec, n_max: int):
    """An empty ``GraphState`` built by one program: every leaf is its own
    device buffer (eager construction reuses one zero scalar for several
    leaves, which buffer donation refuses)."""
    return GraphState(sort=sort_mod.make_sort(sspec),
                      vt=vt_mod.make_vertex_table(n_max),
                      pool=ep.make_edge_pool(pspec))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _defrag(sspec: SortSpec, pspec: ep.PoolSpec, state: GraphState,
            incoming=None):
    pool, vt = ep.defrag(pspec, state.pool, state.vt, incoming)
    return GraphState(state.sort, vt, pool)


# --------------------------------------------------------------------------


@dataclass
class RadixGraph:
    """Dynamic graph store. ``n_max`` vertices / ``pool_blocks`` blocks are
    hard capacities (static shapes); overflow is counted, never UB."""

    n_max: int
    key_bits: int = 32
    expected_n: Optional[int] = None
    layers: Optional[int] = None
    pool_blocks: Optional[int] = None
    block_size: int = 16
    k_max: int = 256
    dmax: int = 4096
    batch: int = 4096          # padded op-batch size
    undirected: bool = False
    probe_width: int = 256     # live-edge probe window (entries per pair)
    k_big: int = 16            # per-batch full-width (dmax) compaction budget
    append_impl: str = "auto"  # 'ref' scatter+window probe | 'pallas' fused
    compact_impl: str = "auto"
    defrag_impl: str = "auto"  # 'stream' block-row rebuild | 'dense' lexsort
    capacity_factor: Optional[float] = None
    policy: str = "snaplog"    # 'snaplog' (paper) | 'grow' | 'sorted' baselines
    buf_blocks: int = 1
    sort_config: Optional[SortConfig] = None  # override the optimizer (baselines)
    pipeline_depth: int = 8    # edge batches staged per flush sync point
    donate_apply: bool = True  # donate the state pytree in steady-state applies
    fuse_scan: bool = False    # fuse each group into ONE lax.scan program

    def __post_init__(self):
        n = self.expected_n or self.n_max
        # paper setting: l = lglg(u) (e.g. 5 for u = 2^32); DP prunes a_i = 0
        l = self.layers or max(2, round(math.log2(max(2, self.key_bits))))
        self.config: SortConfig = self.sort_config or optimize_sort(
            n, self.key_bits, l)
        self.sort_spec = SortSpec.from_config(self.config, self.n_max,
                                              self.capacity_factor)
        nb = self.pool_blocks or max(64, (8 * self.n_max) // self.block_size)
        self.pool_spec = ep.PoolSpec(n_blocks=nb, block_size=self.block_size,
                                     k_max=self.k_max, dmax=self.dmax,
                                     probe_width=self.probe_width,
                                     k_big=self.k_big,
                                     append_impl=self.append_impl,
                                     compact_impl=self.compact_impl,
                                     defrag_impl=self.defrag_impl,
                                     policy=self.policy,
                                     buf_blocks=self.buf_blocks)
        self.state = _fresh_state(self.sort_spec, self.pool_spec, self.n_max)
        # retained MVCC versions: (label, version_ts, state)
        self._versions: list[tuple[int, int, GraphState]] = []
        self.dropped_ops: int = 0  # masked edge ops refused at capacity
        # epoch-cached CSR snapshots: (read_ts, m_cap) -> (state, snapshot).
        # A hit requires identity with the CURRENT state pytree, so every
        # mutation (which necessarily produces a new functional state)
        # invalidates implicitly; mutators also clear the dict explicitly.
        self._snap_cache: dict = {}
        self._epoch: int = 0          # bumped by every mutating op
        self.snapshot_hits: int = 0
        self.snapshot_misses: int = 0
        # maintenance-spike accounting: wall-clock ms spent in ops that
        # paid a global rebuild — explicit defrags and apply batches that
        # triggered one (the tier-L fallback spikes) — and how many did
        self.defrag_ms: float = 0.0
        # defrag_ms split: host-stage (staging + async dispatch) vs the
        # device-blocked sync tail of the spiking window — separable
        # because every spike window already records its stage/sync split
        self.defrag_host_ms: float = 0.0
        self.defrag_sync_ms: float = 0.0
        self._seen_defrags: int = 0
        # pipelined-apply accounting: a flush is one ``_apply_edge_batches``
        # call (= one host wait), a super-batch one dispatch group of up to
        # ``pipeline_depth`` device batches. ``_pinned`` names a state
        # that must not be donated (a captured epoch or MVCC version); the
        # fresh state is a jitted output with distinct leaf buffers, so the
        # very first dispatch already donates.
        self._pinned: Optional[GraphState] = None
        self.pipe_flushes: int = 0
        self.pipe_super_batches: int = 0
        self.pipe_stage_ms: float = 0.0   # host staging + async dispatch
        self.pipe_wait_ms: float = 0.0    # host blocked on the flush's result

    # ---- batching helpers ----
    def _pad(self, arr, fill, dtype):
        a = np.asarray(arr)
        B = self.batch
        n = a.shape[0]
        nb = ((n + B - 1) // B) * B if n else B
        out = np.full((nb,) + a.shape[1:], fill, dtype=dtype)
        if n:
            out[:n] = a
        mask = np.zeros((nb,), bool)
        mask[:n] = True
        return out, mask

    def _key_batches(self, ids):
        ids = np.asarray(ids, np.uint64)
        padded, mask = self._pad(ids, 0, np.uint64)
        for i in range(0, padded.shape[0], self.batch):
            yield (pack_keys(padded[i:i + self.batch], self.key_bits),
                   jnp.asarray(mask[i:i + self.batch]))

    def _invalidate(self):
        """Every mutating op seals a new epoch: cached CSR snapshots of the
        previous epoch are dropped (reads on an UNCHANGED graph keep hitting
        the cache and never rescan the pool)."""
        self._epoch += 1
        self._snap_cache.clear()

    # ---- public API ----
    def add_vertices(self, ids):
        self._invalidate()
        offs = []
        for keys, mask in self._key_batches(ids):
            self.state, off, _ = _add_vertices(self.sort_spec, self.pool_spec,
                                               self.state, keys, mask)
            offs.append(np.asarray(off))
        n = len(np.asarray(ids))
        return np.concatenate(offs)[:n] if offs else np.zeros(0, np.int32)

    def delete_vertices(self, ids):
        self._invalidate()
        for keys, mask in self._key_batches(ids):
            self.state, _, _ = _delete_vertices(self.sort_spec, self.pool_spec,
                                                self.state, keys, mask)

    def lookup(self, ids):
        out = []
        n = len(np.asarray(ids))
        for keys, mask in self._key_batches(ids):
            out.append(np.asarray(_lookup(self.sort_spec, self.pool_spec,
                                          self.state, keys)))
        return np.concatenate(out)[:n] if out else np.zeros(0, np.int32)

    def _edge_super_batches(self, src, dst, w) -> list:
        """The staged flush: interleave, pad, pack and put on the device.
        Super-batches of depth <= ``pipeline_depth``: groups of k flat
        (B, ...) batch tuples by default, or ONE stacked (k, B, ...) tuple
        when ``fuse_scan`` is set (the single-program ``lax.scan`` entry).
        The ragged tail ships at its true depth k' < K (jit retraces per
        distinct k): padding with fully-masked batches would still advance
        the pool clock per batch and break parity with sequential applies."""
        src = np.asarray(src, np.uint64)
        dst = np.asarray(dst, np.uint64)
        w = np.asarray(w, np.float32)
        if self.undirected:
            src, dst, w = interleave_undirected(src, dst, w)
        ps, mask = self._pad(src, 0, np.uint64)
        pd, _ = self._pad(dst, 0, np.uint64)
        pw, _ = self._pad(w, 0, np.float32)
        B = self.batch
        NB = ps.shape[0] // B
        K = max(1, int(self.pipeline_depth))
        sk = pack_keys(ps, self.key_bits)       # one packing pass, reshaped
        dk = pack_keys(pd, self.key_bits)       # into (k, B, 2) slices below
        out = []
        i = 0
        while i < NB:
            k = min(K, NB - i)
            lo, hi = i * B, (i + k) * B
            if k > 1 and self.fuse_scan:
                out.append((jnp.reshape(sk[lo:hi], (k, B, 2)),
                            jnp.reshape(dk[lo:hi], (k, B, 2)),
                            jnp.asarray(pw[lo:hi].reshape(k, B)),
                            jnp.asarray(mask[lo:hi].reshape(k, B))))
            else:
                out.append([(sk[a:a + B], dk[a:a + B],
                             jnp.asarray(pw[a:a + B]),
                             jnp.asarray(mask[a:a + B]))
                            for a in range(lo, hi, B)])
            i += k
        return out

    def _note_spike(self, t0: float, t1: Optional[float] = None):
        """Attribute the finished op's wall time to the spike accounting
        when it paid a global rebuild (the pool's defrags counter
        advanced past the watermark). ``t1`` is the stage->sync boundary
        of the window; the split lands in ``defrag_host_ms`` /
        ``defrag_sync_ms`` (``t1=None`` books everything as host time)."""
        d = int(self.state.pool.defrags)
        if d != self._seen_defrags:
            now = time.perf_counter()
            self.defrag_ms += (now - t0) * 1000.0
            self.defrag_host_ms += ((t1 if t1 is not None else now) - t0) \
                * 1000.0
            if t1 is not None:
                self.defrag_sync_ms += (now - t1) * 1000.0
            self._seen_defrags = d

    def pin_live_state(self):
        """Exempt the CURRENT state pytree from buffer donation. Called
        whenever an external handle may retain the live arrays (epoch
        capture, MVCC checkpoint): the next apply then runs its first
        dispatch through the non-donating program instead of invalidating
        the retained buffers."""
        self._pinned = self.state

    def _apply_edge_batches(self, src, dst, w, req: int = 0):
        """One flush, in three host spans that carry the request number
        ``req``: ``graph.apply.stage`` (``_edge_super_batches``),
        ``graph.apply.dispatch`` (the asynchronous step calls) and
        ``graph.apply.wait`` (the drop-counter fetch, the flush's one
        sync)."""
        self._invalidate()
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("graph.apply.stage", req=req):
            groups = self._edge_super_batches(src, dst, w)
        drops = []
        with jax.profiler.TraceAnnotation("graph.apply.dispatch", req=req):
            for xs in groups:
                if isinstance(xs, list):
                    # default steady state: k flat donated dispatches with
                    # NO host sync between them. Measured faster than the
                    # fused lax.scan program on XLA CPU, where the
                    # loop-carried pool scatters lose the in-place-update
                    # optimization the flat program gets (~4x per batch at
                    # benchmark capacities).
                    for x in xs:
                        donate = self.donate_apply and \
                            (self.state is not self._pinned)
                        fn = _update_edges_donate if donate else _update_edges
                        self.state, d = fn(self.sort_spec, self.pool_spec,
                                           self.state, *x)
                        drops.append(d)        # device scalar — no sync here
                else:
                    donate = self.donate_apply and \
                        (self.state is not self._pinned)
                    fn = _update_edges_pipe_donate if donate \
                        else _update_edges_pipe
                    self.state, d = fn(self.sort_spec, self.pool_spec,
                                       self.state, *xs)
                    drops.append(d)
                self.pipe_super_batches += 1
        self.pipe_stage_ms += (time.perf_counter() - t0) * 1000.0
        t1 = time.perf_counter()
        # ONE host sync per flush: fetching the drop counters forces the
        # whole dispatched chain; the defrag watermark delta then attributes
        # any rebuild spike to this flush window
        with jax.profiler.TraceAnnotation("graph.apply.wait", req=req):
            self.dropped_ops += sum(int(d) for d in drops)
        self.pipe_wait_ms += (time.perf_counter() - t1) * 1000.0
        self.pipe_flushes += 1
        self._note_spike(t0, t1)

    def add_edges(self, src, dst, weight=None):
        w = np.ones(len(np.asarray(src)), np.float32) if weight is None \
            else np.asarray(weight, np.float32)
        assert np.all(w != 0), "weight 0 is the NULL tombstone; use delete_edges"
        self._apply_edge_batches(src, dst, w)

    update_edges = add_edges  # same log-append op (paper: insert == update)

    def delete_edges(self, src, dst):
        w = np.zeros(len(np.asarray(src)), np.float32)  # NULL tombstones
        self._apply_edge_batches(src, dst, w)

    def apply_ops(self, src, dst, weight, req: int = 0):
        """Order-preserving mixed stream: weight==0 deletes, else insert/update
        (the paper's mixed-updates workload, Fig. 9). ``req`` labels the
        flush's trace spans."""
        self._apply_edge_batches(src, dst, np.asarray(weight, np.float32),
                                 req)

    def neighbors(self, ids, width=None, read_ts=None, as_ids=True):
        """Get-neighbors for a batch of vertex IDs (paper: O(d) per vertex).

        The SORT lookup is fused into the jitted read (``step_neighbors``):
        one device dispatch per padded key batch, and the padded batch shape
        keeps the jit cache warm across differently-sized queries."""
        width = width or self.pool_spec.dmax
        n = len(np.asarray(ids))
        ds, ws, cs = [], [], []
        for keys, _ in self._key_batches(ids):
            bd, bw, _, bcnt = _neighbors(self.sort_spec, self.pool_spec,
                                         self.state, keys, width, read_ts)
            ds.append(np.asarray(bd))
            ws.append(np.asarray(bw))
            cs.append(np.asarray(bcnt))
        d = np.concatenate(ds)[:n]
        w = np.concatenate(ws)[:n]
        cnt = np.concatenate(cs)[:n]
        if as_ids:
            # one batched hi/lo gather over the whole (B, width) offset matrix
            # (rows are front-packed, so entries past cnt[i] are -1: clip for
            # the gather, then slice per vertex — never returned)
            ids_np = np.asarray(self.state.vt.ids)
            oc = np.clip(d, 0, ids_np.shape[0] - 1)
            gids = (ids_np[oc, 0].astype(np.uint64) << np.uint64(32)) \
                | ids_np[oc, 1].astype(np.uint64)
            return [(gids[i, :cnt[i]], w[i, :cnt[i]])
                    for i in range(d.shape[0])]
        return [(d[i, :cnt[i]], w[i, :cnt[i]]) for i in range(d.shape[0])]

    def snapshot(self, read_ts=None, m_cap=None) -> GraphSnapshot:
        """Epoch-cached CSR view: repeated snapshots of an unchanged graph
        return the SAME artifact without rescanning the pool; any mutation
        invalidates (``snapshot_hits``/``snapshot_misses`` expose the
        behaviour for tests and the serving layer)."""
        m_cap = m_cap or self.pool_spec.capacity_entries
        key = (None if read_ts is None else int(read_ts), m_cap)
        hit = self._snap_cache.get(key)
        if hit is not None and hit[0] is self.state:
            self.snapshot_hits += 1
            return hit[1]
        self.snapshot_misses += 1
        snap = _snapshot(self.sort_spec, self.pool_spec, m_cap, self.state,
                         read_ts)
        self._snap_cache[key] = (self.state, snap)
        return snap

    def snapshot_at(self, ts: int, m_cap=None) -> GraphSnapshot:
        """Historical CSR snapshot at operation timestamp ``ts``, resolved
        against retained MVCC versions: the answering state is the EARLIEST
        retained version whose version_ts >= ts (compactions after a
        checkpoint may have dropped pre-checkpoint history from newer
        states), falling back to the live state when ``ts`` is newer than
        every checkpoint."""
        if ts >= self.current_ts:
            return self.snapshot(m_cap=m_cap)
        cands = [v for v in self._versions if v[1] >= ts]
        state = min(cands, key=lambda v: v[1])[2] if cands else self.state
        if state is self.state:
            return self.snapshot(read_ts=ts, m_cap=m_cap)
        m_cap = m_cap or self.pool_spec.capacity_entries
        return _snapshot(self.sort_spec, self.pool_spec, m_cap, state, ts)

    @property
    def current_ts(self) -> int:
        """Timestamp of the latest applied operation (clock points one past)."""
        return int(self.state.pool.clock) - 1

    def checkpoint_version(self, label: Optional[int] = None):
        """Retain the current immutable state (MVCC versioned arrays).
        Returns the version timestamp: reads at read_ts=this see exactly the
        current contents."""
        ts = self.current_ts
        self.pin_live_state()       # retained version must never be donated
        self._versions.append((label if label is not None else ts, ts,
                               self.state))
        return ts

    def retain_version(self, state: GraphState, label: int):
        """Retain an ARBITRARY captured state (not necessarily the live
        one) as an MVCC version — the epoch-chain pin: a warm analytics
        entry keeps its epoch's arrays reachable and time-travel-readable
        until ``release_version(label)``. The version timestamp is the
        captured state's own clock."""
        ts = int(state.pool.clock) - 1
        if state is self.state:
            self.pin_live_state()   # retained version must never be donated
        self._versions.append((label, ts, state))
        return ts

    def release_version(self, label: int) -> int:
        """Drop retained MVCC versions with the given label (as returned by /
        passed to ``checkpoint_version``) so their device arrays can be
        freed instead of leaking for the life of the process. Returns the
        number of versions released."""
        kept = [v for v in self._versions if v[0] != label]
        released = len(self._versions) - len(kept)
        self._versions = kept
        return released

    @property
    def retained_versions(self) -> list:
        """(label, version_ts) of every retained MVCC version."""
        return [(lbl, ts) for lbl, ts, _ in self._versions]

    def defrag(self, pending_src=None):
        """Explicit global rebuild. ``pending_src`` optionally names the
        SOURCE vertex IDs of a batch about to be applied (e.g. one that
        just reported drops): the rebuilt extents are pre-sized for those
        pending ops — ``cap >= size + incoming`` per vertex — so freshly
        rebuilt hub extents don't immediately re-overflow into another
        rebuild when the batch is retried."""
        self._invalidate()
        incoming = None
        if pending_src is not None:
            offs = self.lookup(np.asarray(pending_src, np.uint64))
            incoming = jnp.zeros((self.n_max,), jnp.int32).at[
                jnp.asarray(np.where(offs >= 0, offs, self.n_max))].add(
                    1, mode="drop")
        t0 = time.perf_counter()
        self.state = _defrag(self.sort_spec, self.pool_spec, self.state,
                             incoming)
        t1 = time.perf_counter()
        jax.block_until_ready(self.state.pool.dst)
        self._note_spike(t0, t1)

    # ---- introspection ----
    @property
    def num_vertices(self) -> int:
        return int(vt_mod.num_active(self.state.vt))

    @property
    def num_edges(self) -> int:
        """Live edge count from the incrementally-maintained counter — O(1),
        no CSR rebuild. Vertex deletes / capacity drops mark the counter
        dirty; the recount then reuses the (cached) snapshot and writes the
        exact value back."""
        pool = self.state.pool
        if int(pool.live_dirty):
            snap = self.snapshot()
            m = int(snap.m)
            self.state = GraphState(self.state.sort, self.state.vt,
                                    pool._replace(
                                        live_m=jnp.asarray(m, jnp.int32),
                                        live_dirty=jnp.zeros((), jnp.int32)))
            # re-key the cache entry onto the patched (semantically
            # identical) state so the writeback doesn't evict it
            m_cap = self.pool_spec.capacity_entries
            self._snap_cache[(None, m_cap)] = (self.state, snap)
            # the host-side _replace shares device buffers with the
            # pre-patch state (which callers may still hold) — pin so the
            # next apply never donates them
            self.pin_live_state()
            return m
        return int(pool.live_m)

    @property
    def num_defrags(self) -> int:
        """Global pool rebuilds so far — the fast path's fallback counter
        (hub-heavy streams overflowing more than ``k_big`` over-window
        vertices per batch land here; Theorem 2 keeps it O(log) in the op
        count otherwise)."""
        return int(self.state.pool.defrags)

    @property
    def tiles_scanned(self) -> int:
        """Cumulative pool tiles the bounded append visited (touched owner
        extents + landed slots per batch) — certifies the prefetched scan
        bound: it grows with the batches' footprints, never with
        batches x pool size."""
        return int(self.state.pool.tiles_scanned)

    def memory_bytes(self, materialized=True) -> int:
        """Paper-comparable memory: materialized SORT slots (4B), vertex rows
        (32B as in Fig. 3), occupied edge blocks (12B/entry: dst+weight+ts).
        materialized=False reports full static pool allocation instead."""
        if materialized:
            sort_b = int(sort_mod.materialized_slots(self.sort_spec,
                                                     self.state.sort)) * 4
            vrows = int(self.state.vt.num_rows) * 32
            blocks = int(jnp.sum((self.state.pool.owner >= 0).astype(jnp.int32)))
            return sort_b + vrows + blocks * self.pool_spec.block_size * 12
        sort_b = sum(self.sort_spec.pool_sizes()) * 4
        vrows = self.n_max * 32
        return sort_b + vrows + self.pool_spec.capacity_entries * 12

    @property
    def overflowed(self) -> bool:
        return bool(int(self.state.sort.overflow) or int(self.state.vt.overflow)
                    or int(self.state.pool.overflow))
