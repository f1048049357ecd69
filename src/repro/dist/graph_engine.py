"""Vertex-space sharding of RadixGraph over a mesh axis.

Partitioning: ``owner(key) = hash(key) % n_shards`` on the SOURCE vertex —
every edge (u, v, w) lives in u's shard, so one shard holds a vertex's whole
edge array and answers its queries locally (RapidStore-style decoupled
per-partition state). Undirected graphs insert both directions host-side,
exactly like the single-node ``RadixGraph``.

A batched update step under ``shard_map``:

1. each shard hashes its slice of the global op batch and ranks ops into
   per-owner buckets of ``cap`` slots. With the default
   ``capacity_factor=1.0``, ``cap`` equals the per-shard slice, so routing is
   lossless — a source shard can never overflow one owner's bucket with ops
   from its own slice;
2. one ``all_to_all`` exchanges the buckets. With ``pack=True`` the five
   payloads (src hi/lo, dst hi/lo, weight bits, validity) travel as a single
   uint32 word-matrix — one collective launch instead of four;
3. each shard applies its received ops with the SAME pure transition the
   single-shard path uses (``core.radixgraph.step_update_edges``), returning
   a per-shard ``dropped`` count (capacity refusals, never UB).

Queries (``make_khop_counts``) route identically and the owner's answers ride
a second all_to_all back to the asking shard, which restores request order.

All functions close over static specs, so a jitted engine step is one fused
SPMD program: route -> exchange -> apply, no host round-trips.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro import analytics as alg
from repro.core import edgepool as ep
from repro.core import radixgraph as rg
from repro.core import sort as sort_mod
from repro.core import vertex_table as vt_mod
from repro.core.radixgraph import GraphState
from repro.core.sort import SortSpec

__all__ = ["make_sharded_state", "make_apply_edges",
           "make_apply_edges_pipelined", "make_khop_counts",
           "make_sync_vertices", "make_snapshot", "make_bfs", "make_pagerank",
           "make_wcc", "make_sssp", "make_bc",
           "collect_owner_values", "shard_of_keys"]


def shard_of_keys(keys: jnp.ndarray, n_shards: int) -> jnp.ndarray:
    """Owner shard of each (..., 2) uint32 key — a cheap multiplicative hash
    with an xor-shift finalizer so dense ID ranges still spread evenly."""
    hi = keys[..., 0]
    lo = keys[..., 1]
    h = lo * jnp.uint32(0x9E3779B1) + hi * jnp.uint32(0x85EBCA77)
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x7FEB352D)
    h = h ^ (h >> 15)
    return (h % jnp.uint32(n_shards)).astype(jnp.int32)


def make_sharded_state(sspec: SortSpec, pspec: ep.PoolSpec, n_shards: int,
                       n_per_shard: int, mesh=None,
                       axis: Optional[str] = None) -> GraphState:
    """Fresh per-shard (SortState, VertexTable, EdgePool) pytrees stacked on
    a leading shard dim — the input/output carried by the engine's jitted
    step functions (shard dim maps onto the mesh axis). Given ``mesh`` and
    ``axis``, the state is built by one program whose outputs are sharded
    over that axis, so each device materializes only its own shards."""
    def build():
        one = GraphState(
            sort=sort_mod.make_sort(sspec),
            vt=vt_mod.make_vertex_table(n_per_shard),
            pool=ep.make_edge_pool(pspec),
        )
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (n_shards,) + x.shape), one)

    if mesh is None:
        return build()
    return jax.jit(build, out_shardings=NamedSharding(mesh, P(axis)))()


def _bucket_slots(owner: jnp.ndarray, valid: jnp.ndarray, cap: int):
    """Slot of each op in per-destination buckets of ``cap`` entries.

    Returns (slot, ok): ``slot = owner * cap + rank`` where rank is the op's
    stable order among same-owner ops; ``ok`` is False for invalid ops and
    bucket overflow (rank >= cap).
    """
    B = owner.shape[0]
    SENT = jnp.int32(0x7FFFFFFF)
    key = jnp.where(valid, owner, SENT)
    order = jnp.argsort(key, stable=True)
    so = key[order]
    idx = jnp.arange(B, dtype=jnp.int32)
    first = jnp.concatenate([jnp.ones((1,), bool), so[1:] != so[:-1]])
    start = jax.lax.cummax(jnp.where(first, idx, 0))
    rank_sorted = idx - start
    rank = jnp.zeros((B,), jnp.int32).at[order].set(rank_sorted)
    ok = valid & (rank < cap)
    return owner * cap + rank, ok


def _scatter_rows(x: jnp.ndarray, tgt: jnp.ndarray, n_rows: int, fill):
    out = jnp.full((n_rows,) + x.shape[1:], fill, x.dtype)
    return out.at[tgt].set(x, mode="drop")


# --------------------------------------------------------------------------
# frontier-compacted exchange
#
# The dense exchanges below move ``n_shards x n_cap`` buffers per round even
# when only a handful of rows carry data (sparse BFS frontiers, incremental
# vertex syncs). The compacted variant routes only the masked rows into
# count-prefixed buckets of a static ``budget`` rows per destination shard;
# a replicated psum decides OVERFLOW up front, and the caller conds into the
# dense path for that round, so results are bit-exact either way.
# --------------------------------------------------------------------------

def _route_overflow(owner, mask, n: int, budget: int, axis: str):
    """Replicated: does any shard route > budget rows to one destination?"""
    counts = jnp.zeros((n,), jnp.int32).at[
        jnp.where(mask, owner, n)].add(1, mode="drop")
    over = jnp.any(counts > budget).astype(jnp.int32)
    return jax.lax.psum(over, axis) > 0


def _route_dense(owner, mask, payload, n: int, cap: int, a2a):
    """Lossless dense route: bucket capacity ``cap`` rows per destination,
    validity as a trailing flag column. Returns (rows (n*cap, C), valid)."""
    C = payload.shape[1]
    slot, ok = _bucket_slots(owner, mask, cap)
    p = jnp.concatenate([payload, ok.astype(jnp.uint32)[:, None]], axis=1)
    buf = _scatter_rows(p, jnp.where(ok, slot, n * cap), n * cap, 0)
    r = a2a(buf.reshape(n, cap, C + 1)).reshape(n * cap, C + 1)
    return r[:, :C], r[:, C] == 1


def _route_compact(owner, mask, payload, n: int, budget: int, a2a):
    """Count-prefixed compacted route: per destination shard one header row
    (its [0] word = row count) + ``budget`` data rows. The caller must have
    established via ``_route_overflow`` that no bucket spills.
    Returns (rows (n*budget, C), valid)."""
    C = payload.shape[1]
    stride = budget + 1
    slot, ok = _bucket_slots(owner, mask, budget)
    # data row at owner*stride + 1 + rank; slot//budget == owner for ok rows
    tgt = jnp.where(ok, slot + slot // budget + 1, n * stride)
    counts = jnp.zeros((n,), jnp.uint32).at[
        jnp.where(ok, owner, n)].add(1, mode="drop")
    buf = jnp.zeros((n * stride, C), jnp.uint32).at[tgt].set(
        payload.astype(jnp.uint32), mode="drop")
    buf = buf.at[jnp.arange(n) * stride, 0].set(counts)
    r = a2a(buf.reshape(n, stride, C))
    cnt = r[:, 0, 0].astype(jnp.int32)
    rows = r[:, 1:, :].reshape(n * budget, C)
    valid = (jnp.arange(budget, dtype=jnp.int32)[None, :] <
             cnt[:, None]).reshape(-1)
    return rows, valid


def _pack_qbits(b: jnp.ndarray) -> jnp.ndarray:
    """(R, Q) bool -> (R, ceil(Q/32)) uint32 word matrix (bit q of word
    q//32). Distinct powers of two make the sum an OR."""
    R, Q = b.shape
    QW = (Q + 31) // 32
    bp = jnp.pad(b, ((0, 0), (0, QW * 32 - Q))).reshape(R, QW, 32)
    weights = jnp.uint32(1) << jnp.arange(32, dtype=jnp.uint32)
    return jnp.sum(bp.astype(jnp.uint32) * weights[None, None, :], axis=-1)


def _unpack_qbits(words: jnp.ndarray, Q: int) -> jnp.ndarray:
    R, QW = words.shape
    bits = (words[:, :, None] >>
            jnp.arange(32, dtype=jnp.uint32)[None, None, :]) & 1
    return bits.reshape(R, QW * 32)[:, :Q] == 1


def _popcount_rows(words: jnp.ndarray) -> jnp.ndarray:
    """Per-row set-bit count of a (R, W) uint32 word matrix."""
    return jnp.sum(jax.lax.population_count(words).astype(jnp.int32),
                   axis=-1)


def make_apply_edges(sspec: SortSpec, pspec: ep.PoolSpec, mesh, axis: str,
                     pack: bool = True, capacity_factor: float = 1.0,
                     route_budget: Optional[int] = None):
    """Build ``apply(state, src_keys, dst_keys, w, mask) -> (state, dropped)``.

    Inputs are GLOBAL batches: (B, 2) uint32 keys, (B,) f32 weights (0 =
    delete), (B,) bool mask, with B divisible by the shard count; ``state``
    is a ``make_sharded_state`` pytree. ``dropped`` is int32[n_shards] —
    per-shard refused ops (routing overflow when capacity_factor < 1, vertex
    table / pool exhaustion otherwise).

    ``route_budget`` compacts the op exchange: ops ride count-prefixed
    buckets of that many rows per destination shard (cutting collective
    bytes when the hash spread is even), falling back to the dense lossless
    route — still applied through the SAME pure transition — whenever a
    bucket would spill. Lossless either way, so ``dropped`` keeps meaning
    capacity refusals only.
    """
    n = int(mesh.shape[axis])
    apply_one = _make_shard_batch_apply(sspec, pspec, n, axis, pack,
                                        capacity_factor, route_budget)

    def body(state, sk, dk, w, mask):
        g = jax.tree.map(lambda x: x[0], state)
        g, dropped = apply_one(g, sk, dk, w, mask)
        return jax.tree.map(lambda x: x[None], g), dropped[None]

    sharded = jax.shard_map(body, mesh=mesh,
                            in_specs=(P(axis), P(axis), P(axis), P(axis),
                                      P(axis)),
                            out_specs=(P(axis), P(axis)), check_vma=False)

    def apply_edges(state, src_keys, dst_keys, w, mask):
        B = src_keys.shape[0]
        assert B % n == 0, f"global op batch {B} not divisible by {n} shards"
        return sharded(state, src_keys, dst_keys, w, mask)

    return apply_edges


def _make_shard_batch_apply(sspec: SortSpec, pspec: ep.PoolSpec, n: int,
                            axis: str, pack: bool, capacity_factor: float,
                            route_budget: Optional[int]):
    """Shard-local routed apply of ONE op batch, shared by the per-batch and
    pipelined engine factories: ``(g, sk, dk, w, mask) -> (g, dropped)`` with
    unstacked per-shard state ``g`` and a scalar ``dropped``."""

    def apply_one(g, sk, dk, w, mask):
        Bl = sk.shape[0]
        cap = max(1, int(round(Bl * capacity_factor)))
        owner = shard_of_keys(sk, n)
        a2a = functools.partial(jax.lax.all_to_all, axis_name=axis,
                                split_axis=0, concat_axis=0)
        if route_budget is not None:
            payload = jnp.stack(
                [sk[:, 0], sk[:, 1], dk[:, 0], dk[:, 1],
                 jax.lax.bitcast_convert_type(w, jnp.uint32)], axis=-1)

            def apply_rows(rows, valid):
                rw = jax.lax.bitcast_convert_type(rows[:, 4], jnp.float32)
                return rg.step_update_edges(sspec, pspec, g, rows[:, 0:2],
                                            rows[:, 2:4], rw, valid)

            ovf = _route_overflow(owner, mask, n, route_budget, axis)
            return jax.lax.cond(
                ovf,
                lambda _: apply_rows(*_route_dense(owner, mask, payload, n,
                                                   Bl, a2a)),
                lambda _: apply_rows(*_route_compact(owner, mask, payload,
                                                     n, route_budget, a2a)),
                None)
        slot, ok = _bucket_slots(owner, mask, cap)
        route_drop = jnp.sum((mask & ~ok).astype(jnp.int32))
        NC = n * cap
        tgt = jnp.where(ok, slot, NC)
        if pack:
            payload = jnp.stack(
                [sk[:, 0], sk[:, 1], dk[:, 0], dk[:, 1],
                 jax.lax.bitcast_convert_type(w, jnp.uint32),
                 ok.astype(jnp.uint32)], axis=-1)            # (Bl, 6) u32
            buf = _scatter_rows(payload, tgt, NC, 0)
            r = a2a(buf.reshape(n, cap, 6)).reshape(NC, 6)
            rsk, rdk = r[:, 0:2], r[:, 2:4]
            rw = jax.lax.bitcast_convert_type(r[:, 4], jnp.float32)
            rmask = r[:, 5] == 1
        else:
            def xch(x, fill):
                buf = _scatter_rows(x, tgt, NC, fill)
                return a2a(buf.reshape((n, cap) + x.shape[1:])).reshape(
                    (NC,) + x.shape[1:])
            rsk = xch(sk, 0)
            rdk = xch(dk, 0)
            rw = xch(w, 0.0)
            rmask = xch(ok.astype(jnp.uint32), 0) == 1
        g, dropped = rg.step_update_edges(sspec, pspec, g, rsk, rdk, rw,
                                          rmask)
        return g, dropped + route_drop

    return apply_one


def make_apply_edges_pipelined(sspec: SortSpec, pspec: ep.PoolSpec, mesh,
                               axis: str, pack: bool = True,
                               capacity_factor: float = 1.0,
                               route_budget: Optional[int] = None):
    """Build ``apply(state, src_keys, dst_keys, w, mask) -> (state, dropped)``
    over a STACKED (K, B, ...) super-batch: one ``lax.scan`` of the routed
    per-batch transition inside a single shard_map program, so K batches cost
    ONE dispatch and zero host round-trips mid-stream.

    Identical semantics to K sequential ``make_apply_edges`` calls (same
    routing, same ``step_update_edges``, same overflow-defrag fallback — all
    device-side), with the drop counter accumulated on device and returned as
    one int32[n_shards] summed over the K batches. ``tiles_scanned`` /
    ``defrags`` likewise accumulate in the pool scalars, so callers fetch
    stats once per flush instead of once per batch.
    """
    n = int(mesh.shape[axis])
    apply_one = _make_shard_batch_apply(sspec, pspec, n, axis, pack,
                                        capacity_factor, route_budget)

    def body(state, sks, dks, ws, masks):
        g = jax.tree.map(lambda x: x[0], state)

        def step(gc, xs):
            return apply_one(gc, *xs)

        g, drops = jax.lax.scan(step, g, (sks, dks, ws, masks))
        return (jax.tree.map(lambda x: x[None], g),
                jnp.sum(drops, dtype=jnp.int32)[None])

    sharded = jax.shard_map(body, mesh=mesh,
                            in_specs=(P(axis), P(None, axis), P(None, axis),
                                      P(None, axis), P(None, axis)),
                            out_specs=(P(axis), P(axis)), check_vma=False)

    def apply_edges_pipelined(state, src_keys, dst_keys, w, mask):
        K, B = src_keys.shape[0], src_keys.shape[1]
        assert B % n == 0, f"global op batch {B} not divisible by {n} shards"
        assert w.shape == (K, B) and mask.shape == (K, B)
        return sharded(state, src_keys, dst_keys, w, mask)

    return apply_edges_pipelined


def make_khop_counts(sspec: SortSpec, pspec: ep.PoolSpec, mesh, axis: str,
                     k: int = 1, read_ts: Optional[int] = None,
                     m_cap: Optional[int] = None,
                     frontier_budget: Optional[int] = None):
    """Build ``khop(state, query_keys) -> int32[Q]``: live (deduplicated)
    k-hop neighbourhood counts for arbitrary query keys. Queries are routed
    with the same hash partition as updates.

    k == 1 with ``m_cap=None`` answers out-degree straight off the owner's
    edge array (0 for absent vertices, self-loops count) with a route +
    return all_to_all — the degree-query fast path. With ``m_cap`` set,
    k == 1 runs the frontier body below instead, matching
    ``analytics.khop`` exactly (distinct neighbors, source/self-loop
    excluded).

    k in (2, 3) runs BOUNDED frontier rounds over per-shard CSR snapshots
    (requires ``m_cap`` and a vertex-SYNCED state): every round each shard
    expands all queries' frontiers through its local CSR, discoveries ride
    ONE exchange as (id, query-bitmask-words) rows — compacted under
    ``frontier_budget`` with dense fallback — and owners dedup/mark them.
    The count is Σ visited owner rows (psum-replicated) minus the source,
    matching ``analytics.khop``: distinct vertices within <= k hops,
    source excluded; 0 for absent sources."""
    n = int(mesh.shape[axis])
    if k not in (1, 2, 3):
        raise NotImplementedError("khop counts support k <= 3 (bounded "
                                  "frontier rounds)")
    if k > 1 and m_cap is None:
        raise ValueError("k >= 2 requires m_cap for the CSR snapshot")

    def body_degree(state, qk):
        g = jax.tree.map(lambda x: x[0], state)
        Ql = qk.shape[0]
        owner = shard_of_keys(qk, n)
        slot, _ = _bucket_slots(owner, jnp.ones((Ql,), bool), Ql)
        a2a = functools.partial(jax.lax.all_to_all, axis_name=axis,
                                split_axis=0, concat_axis=0)
        buf = _scatter_rows(qk, slot, n * Ql, 0)
        recv = a2a(buf.reshape(n, Ql, 2)).reshape(n * Ql, 2)
        # unrouted slots hold key 0: their answers are never read back
        cnt = rg.step_degree_counts(sspec, pspec, g, recv, read_ts=read_ts)
        back = a2a(cnt.reshape(n, Ql)).reshape(-1)
        return back[slot]

    def body_khop(state, qk):
        g = jax.tree.map(lambda x: x[0], state)
        n_cap = g.vt.del_time.shape[0]
        Ql = qk.shape[0]
        Qtot = n * Ql
        QW = (Qtot + 31) // 32
        a2a = functools.partial(jax.lax.all_to_all, axis_name=axis,
                                split_axis=0, concat_axis=0)
        snap = rg.step_snapshot(sspec, pspec, m_cap, g, read_ts)
        edges = alg.csr_edges(snap)
        my, rowlive, owner, _mine = _row_meta(sspec, g, n, axis)

        # route queries to their owner shards (source rows live there). The
        # slot is (owner, source-local index) — NOT a bucket rank — so the
        # receiver-side position (source shard, index) names each query
        # GLOBALLY: every shard's visited/frontier bit q means the same
        # query, which the final psum relies on.
        qowner = shard_of_keys(qk, n)
        idx = jnp.arange(Ql, dtype=jnp.int32)
        # a validity column rides along: an unrouted slot holds key (0, 0),
        # which would otherwise alias a real vertex id 0 and seed its
        # neighborhood into the psum'd counts of the query sharing the slot
        qpay = jnp.concatenate([qk, jnp.ones((Ql, 1), jnp.uint32)], axis=1)
        buf = _scatter_rows(qpay, qowner * Ql + idx, Qtot, 0)
        recv = a2a(buf.reshape(n, Ql, 3)).reshape(Qtot, 3)
        roff = jnp.where(recv[:, 2] == 1,
                         sort_mod.lookup(sspec, g.sort, recv[:, 0:2]), -1)
        qidx = jnp.arange(Qtot, dtype=jnp.int32)
        # per-query visited/frontier carries are BITMAP-PACKED: uint32
        # words over vertex offsets ((Qtot, n_cap/32) instead of the
        # (Qtot, n_cap) bool slabs), 32x less carried state at pod-scale
        # query batches; expansion transiently unpacks one frontier at a
        # time and the final count is a popcount, so the packed loop is
        # value-identical to the bool one (the parity tests against
        # ``analytics.khop`` pin that down)
        VW = (n_cap + 31) // 32
        visited_w = jnp.zeros((Qtot, VW + 1), jnp.uint32).at[
            qidx, jnp.where(roff >= 0, roff >> 5, VW)].set(
                jnp.where(roff >= 0,
                          jnp.uint32(1) << (roff & 31).astype(jnp.uint32),
                          jnp.uint32(0)))[:, :VW]
        frontier_w = visited_w

        payload_ids = jnp.stack([g.vt.ids[:, 0], g.vt.ids[:, 1]], axis=-1)

        def mark(rows, valid):
            ro = sort_mod.lookup(sspec, g.sort, rows[:, 0:2])
            okr = valid & (ro >= 0)
            flags = _unpack_qbits(rows[:, 2:], Qtot) & okr[:, None]
            hit = jnp.zeros((n_cap + 1, Qtot), bool).at[
                jnp.where(okr, ro, n_cap)].max(flags)
            return hit[:n_cap].T    # (Qtot, n_cap), owner rows only

        for _hop in range(k):
            frontier = _unpack_qbits(frontier_w, n_cap)   # transient
            exp = jax.vmap(lambda f: alg.bfs_expand(snap, f, edges))(frontier)
            qwords = _pack_qbits(exp.T)            # (n_cap, QW)
            mask_rows = rowlive & jnp.any(exp, axis=0)
            payload = jnp.concatenate([payload_ids, qwords], axis=1)
            if frontier_budget is None:
                hit = mark(*_route_dense(owner, mask_rows, payload, n,
                                         n_cap, a2a))
            else:
                ovf = _route_overflow(owner, mask_rows, n, frontier_budget,
                                      axis)
                hit = jax.lax.cond(
                    ovf,
                    lambda _: mark(*_route_dense(owner, mask_rows, payload,
                                                 n, n_cap, a2a)),
                    lambda _: mark(*_route_compact(owner, mask_rows, payload,
                                                   n, frontier_budget, a2a)),
                    None)
            frontier_w = _pack_qbits(hit) & ~visited_w
            visited_w = visited_w | frontier_w

        counts = jax.lax.psum(_popcount_rows(visited_w), axis)
        counts = jnp.maximum(counts - 1, 0)  # drop the source; absent -> 0
        return counts[my * Ql + idx]         # psum-replicated: no return hop

    body = body_degree if (k == 1 and m_cap is None) else body_khop
    sharded = jax.shard_map(body, mesh=mesh, in_specs=(P(axis), P(axis)),
                            out_specs=P(axis), check_vma=False)

    def khop(state, query_keys):
        Q = query_keys.shape[0]
        assert Q % n == 0, f"query batch {Q} not divisible by {n} shards"
        return sharded(state, query_keys)

    return khop


# --------------------------------------------------------------------------
# distributed read path: per-shard CSR snapshots + level-synchronous
# analytics with frontier / inflow exchange over the mesh axis
#
# Edges live in the SOURCE vertex's shard, so a shard's CSR covers exactly
# its local rows; a vertex that only appears as a destination has stub rows
# (no edges) in source shards. ``make_sync_vertices`` registers every live
# row's ID at its hash-owner so that each vertex has exactly one OWNER row —
# the row analytics results are accumulated at and read from.
# --------------------------------------------------------------------------

def _row_meta(sspec, g: GraphState, n: int, axis: str):
    """Per-local-row metadata shared by the distributed analytics bodies."""
    my = jax.lax.axis_index(axis)
    rowlive = g.vt.del_time == 0
    owner = shard_of_keys(g.vt.ids, n)
    return my, rowlive, owner, rowlive & (owner == my)


def make_sync_vertices(sspec: SortSpec, pspec: ep.PoolSpec, mesh, axis: str,
                       budget: Optional[int] = None,
                       incremental: bool = False):
    """Build ``sync(state) -> state``: every live local row's vertex ID is
    routed to its hash-owner shard and locate-or-inserted there, so each
    vertex gains an owner row even if it only ever appeared as an edge
    destination. Idempotent; run once before distributed analytics.

    ``incremental=True`` builds ``sync(state, prev_rows) -> state`` instead:
    only rows with index >= ``prev_rows[shard]`` (i.e. created since the
    caller last synced — valid while vertex rows are never recycled, which
    holds for delete-free services) are exchanged, so steady-state syncs
    cost O(new vertices). With ``budget`` set, the exchange ships
    count-prefixed compacted buckets of that many rows per destination and
    falls back to the dense lossless route when a bucket would spill."""
    n = int(mesh.shape[axis])

    def body(state, *prev):
        g = jax.tree.map(lambda x: x[0], state)
        n_cap = g.vt.del_time.shape[0]
        rowlive = g.vt.del_time == 0
        if incremental:
            prev_rows = prev[0][0]
            rowlive = rowlive & (jnp.arange(n_cap, dtype=jnp.int32) >=
                                 prev_rows)
        owner = shard_of_keys(g.vt.ids, n)
        a2a = functools.partial(jax.lax.all_to_all, axis_name=axis,
                                split_axis=0, concat_axis=0)
        payload = jnp.stack([g.vt.ids[:, 0], g.vt.ids[:, 1]], axis=-1)

        def register(rows, valid):
            st, vt, _, _ = vt_mod.ensure_vertices(sspec, g.sort, g.vt,
                                                  rows[:, 0:2], valid)
            return GraphState(st, vt, g.pool)

        if budget is None:
            g2 = register(*_route_dense(owner, rowlive, payload, n, n_cap,
                                        a2a))
        else:
            ovf = _route_overflow(owner, rowlive, n, budget, axis)
            g2 = jax.lax.cond(
                ovf,
                lambda _: register(*_route_dense(owner, rowlive, payload, n,
                                                 n_cap, a2a)),
                lambda _: register(*_route_compact(owner, rowlive, payload,
                                                   n, budget, a2a)),
                None)
        return jax.tree.map(lambda x: x[None], g2)

    in_specs = (P(axis),) + ((P(axis),) if incremental else ())
    sharded = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                            out_specs=P(axis), check_vma=False)
    return sharded


def make_snapshot(sspec: SortSpec, pspec: ep.PoolSpec, mesh, axis: str,
                  m_cap: int, read_ts: Optional[int] = None):
    """Build ``snap(state) -> GraphSnapshot`` with a leading shard dim: each
    shard builds the CSR of ITS slice of the edge set (dst column holds
    local row offsets) under shard_map — the distributed analogue of
    ``RadixGraph.snapshot``, one fused SPMD program, no host gather."""

    def body(state):
        g = jax.tree.map(lambda x: x[0], state)
        snap = rg.step_snapshot(sspec, pspec, m_cap, g, read_ts)
        return jax.tree.map(lambda x: x[None], snap)

    return jax.shard_map(body, mesh=mesh, in_specs=(P(axis),),
                         out_specs=P(axis), check_vma=False)


def make_bfs(sspec: SortSpec, pspec: ep.PoolSpec, mesh, axis: str,
             m_cap: int, max_iters: int = 32,
             frontier_budget: Optional[int] = None):
    """Build ``bfs(state, source_key) -> int32[n_shards, n_cap]`` — level-
    synchronous distributed BFS. Per level each shard expands its LOCAL CSR
    (``analytics.bfs_expand``), then newly-discovered row IDs are exchanged
    to their owner shards, which mark depth and seed the next frontier.
    Depths are authoritative at owner rows (-1 unreachable); stub rows may
    record the level their shard first saw the vertex. Run on a
    vertex-synced state (``make_sync_vertices``).

    ``frontier_budget`` compacts the per-level exchange: discoveries ship in
    count-prefixed buckets of that many rows per destination shard (dense
    rounds fall back to the lossless n_cap route, decided by a replicated
    psum per level, so depths stay bit-exact)."""
    n = int(mesh.shape[axis])

    def body(state, source_key):
        g = jax.tree.map(lambda x: x[0], state)
        n_cap = g.vt.del_time.shape[0]
        snap = rg.step_snapshot(sspec, pspec, m_cap, g, None)
        edges = alg.csr_edges(snap)   # loop-invariant: built once, not per level
        my, rowlive, owner, _mine = _row_meta(sspec, g, n, axis)
        a2a = functools.partial(jax.lax.all_to_all, axis_name=axis,
                                split_axis=0, concat_axis=0)
        payload = jnp.stack([g.vt.ids[:, 0], g.vt.ids[:, 1]], axis=-1)

        def mark_hits(rows, valid):
            roff = sort_mod.lookup(sspec, g.sort, rows[:, 0:2])
            seen = valid & (roff >= 0)
            return jnp.zeros((n_cap + 1,), bool).at[
                jnp.where(seen, roff, n_cap)].max(True)[:n_cap]

        def exchange(new_local):
            if frontier_budget is None:
                return mark_hits(*_route_dense(owner, new_local, payload, n,
                                               n_cap, a2a))
            ovf = _route_overflow(owner, new_local, n, frontier_budget, axis)
            return jax.lax.cond(
                ovf,
                lambda _: mark_hits(*_route_dense(owner, new_local, payload,
                                                  n, n_cap, a2a)),
                lambda _: mark_hits(*_route_compact(owner, new_local,
                                                    payload, n,
                                                    frontier_budget, a2a)),
                None)

        off0 = sort_mod.lookup(sspec, g.sort, source_key[None, :])[0]
        row = jnp.arange(n_cap, dtype=jnp.int32)
        depth0 = jnp.where(row == off0, 0, -1)
        frontier0 = (row == off0) & rowlive
        go0 = jax.lax.psum(jnp.any(frontier0).astype(jnp.int32), axis) > 0

        def cond(c):
            _, _, it, go = c
            return go & (it < max_iters)

        def lvl(c):
            depth, frontier, it, _ = c
            new_local = alg.bfs_expand(snap, frontier, edges) & (depth < 0)
            # stub rows are marked locally (each row notifies at most once);
            # owner rows are marked via the exchange below, which also
            # dedups discoveries arriving from several shards at once
            hit = exchange(new_local)
            depth = jnp.where(new_local & (owner != my), it + 1, depth)
            nxt = hit & (depth < 0)
            depth = jnp.where(nxt, it + 1, depth)
            go = jax.lax.psum(jnp.any(nxt).astype(jnp.int32), axis) > 0
            return depth, nxt, it + 1, go

        depth, _, _, _ = jax.lax.while_loop(
            cond, lvl, (depth0, frontier0, jnp.int32(0), go0))
        return depth[None]

    sharded = jax.shard_map(body, mesh=mesh, in_specs=(P(axis), P()),
                            out_specs=P(axis), check_vma=False)
    return sharded


def _owner_value_route(sspec, g: GraphState, n: int, axis: str, a2a, owner,
                       rowlive, budget: Optional[int], impl):
    """Run ``impl(rtgt, fwd, bwd)`` under the live-row -> owner-row exchange
    shared by every iterative combine loop (PageRank inflow, WCC labels,
    SSSP distances, BC sigma/delta).

    The route is data-independent — every live local row ships to its
    hash-owner's shard — so it is resolved ONCE per program: a key exchange
    binds each receiver slot to one of the receiver's own rows (``rtgt``),
    and per iteration only VALUES move:

      ``fwd(vals)``   (n_cap, C) per-local-row values -> (R, C) routed rows
                      at the receiver, aligned with ``rtgt`` (combine with a
                      ``.at[rtgt].add/min`` scatter; slot n_cap is the dump);
      ``bwd(merged)`` (n_cap + 1, C) owner-merged values -> ((n_cap, C), ok):
                      every routed row reads its owner's merged value back
                      over the inverse all_to_all (``ok`` marks routed rows).

    With ``budget`` set the exchange ships count-prefixed compacted buckets
    (``_route_compact``) whenever no bucket spills — decided by ONE
    replicated psum up front, since the route never changes mid-run — and
    falls back to the dense lossless layout otherwise, so results are
    identical either way."""
    n_cap = g.vt.del_time.shape[0]
    keys2 = jnp.stack([g.vt.ids[:, 0], g.vt.ids[:, 1]], axis=-1)

    def build(compact: bool):
        if compact:
            F = budget
            stride = F + 1
            rows, valid = _route_compact(owner, rowlive, keys2, n, F, a2a)
            slot, ok = _bucket_slots(owner, rowlive, F)
            tgt = jnp.where(ok, slot + slot // F + 1, n * stride)
        else:
            F = n_cap
            stride = n_cap
            rows, valid = _route_dense(owner, rowlive, keys2, n, n_cap, a2a)
            slot, ok = _bucket_slots(owner, rowlive, n_cap)
            tgt = jnp.where(ok, slot, n * stride)
        R = n * F
        roff = sort_mod.lookup(sspec, g.sort, rows[:, 0:2])
        rtgt = jnp.where(valid & (roff >= 0), roff, n_cap)
        tgtc = jnp.clip(tgt, 0, n * stride - 1)

        def fwd(vals):
            C = vals.shape[1]
            vbuf = jnp.zeros((n * stride, C), vals.dtype).at[tgt].set(
                vals, mode="drop")
            r = a2a(vbuf.reshape(n, stride, C))
            if compact:
                r = r[:, 1:, :]
            return r.reshape(R, C)

        def bwd(merged):
            ans = merged[rtgt]                                    # (R, C)
            C = ans.shape[1]
            if compact:
                abuf = jnp.zeros((n, stride, C), ans.dtype).at[
                    :, 1:, :].set(ans.reshape(n, F, C))
                back = a2a(abuf).reshape(n * stride, C)
            else:
                back = a2a(ans.reshape(n, stride, C)).reshape(n * stride, C)
            return back[tgtc], ok

        return rtgt, fwd, bwd

    if budget is None:
        return impl(*build(False))
    ovf = _route_overflow(owner, rowlive, n, budget, axis)
    return jax.lax.cond(ovf,
                        lambda _: impl(*build(False)),
                        lambda _: impl(*build(True)), None)


def make_pagerank(sspec: SortSpec, pspec: ep.PoolSpec, mesh, axis: str,
                  m_cap: int, iters: int = 20, damping: float = 0.85,
                  frontier_budget: Optional[int] = None,
                  tol: Optional[float] = None, warm: bool = False):
    """Build ``pr(state) -> float32[n_shards, n_cap]`` — distributed
    PageRank. Ranks live at owner rows; per iteration each shard scatters
    contributions along its local CSR (``analytics.pagerank_scatter``) and
    routes every live row's accumulated inflow back to the row's owner over
    one all_to_all (the combine phase). Dangling mass and the active count
    are psums over owner rows. Run on a vertex-synced state.

    ``tol=None`` (default) is the fixed-``iters`` scan — bit-identical to
    the pre-incremental program. ``tol`` set switches to a convergence
    while_loop (stop when the owner-row ``max|Δpr|`` pmax drops under
    ``tol``, ``iters`` now a cap) returning ``(pr, iters_run)``; ``warm``
    additionally takes a ``(n_shards, n_cap)`` float32 seed (negative =
    no previous value, start uniform) — the damped map has ONE fixed
    point, so warm and cold starts converge to the same answer and the
    warm program is the epoch-advance path.

    The inflow route is data-independent (every live row -> its owner), so
    with ``frontier_budget`` the whole run compacts when the live rows fit
    the budget (one replicated psum up front; otherwise the dense route runs
    unchanged). Per-target add order is preserved, so ranks match the dense
    path bit-for-bit."""
    n = int(mesh.shape[axis])
    assert not (warm and tol is None), "warm PageRank needs a tol"

    def body(state, *extra):
        g = jax.tree.map(lambda x: x[0], state)
        n_cap = g.vt.del_time.shape[0]
        snap = rg.step_snapshot(sspec, pspec, m_cap, g, None)
        edges = alg.csr_edges(snap)   # loop-invariant: built once, not per iter
        my, rowlive, owner, mine = _row_meta(sspec, g, n, axis)
        deg = (snap.indptr[1:] - snap.indptr[:-1]).astype(jnp.float32)
        a2a = functools.partial(jax.lax.all_to_all, axis_name=axis,
                                split_axis=0, concat_axis=0)

        n_act = jnp.maximum(jax.lax.psum(
            jnp.sum(mine.astype(jnp.float32)), axis), 1.0)
        pr0 = jnp.where(mine, 1.0 / n_act, 0.0)
        if warm:
            w0 = extra[0][0]
            pr0 = jnp.where(mine & (w0 >= 0), w0, pr0)

        def impl(rtgt, fwd, bwd):
            def one(pr):
                contrib = alg.pagerank_contrib(snap, pr)
                local_in = alg.pagerank_scatter(snap, contrib, edges)
                rv = fwd(local_in[:, None])[:, 0]
                inflow = jnp.zeros((n_cap + 1,)).at[rtgt].add(rv)[:n_cap]
                dangling = jax.lax.psum(
                    jnp.sum(jnp.where(mine & (deg == 0), pr, 0.0)), axis)
                return jnp.where(mine, (1 - damping) / n_act +
                                 damping * (inflow + dangling / n_act), 0.0)

            if tol is None:
                pr, _ = jax.lax.scan(lambda pr, _: (one(pr), None), pr0,
                                     None, length=iters)
                return pr

            def cond(c):
                _, ch, it = c
                return (ch >= tol) & (it < iters)

            def step(c):
                pr, _, it = c
                nxt = one(pr)
                ch = jax.lax.pmax(jnp.max(jnp.where(
                    mine, jnp.abs(nxt - pr), 0.0)), axis)
                return nxt, ch, it + 1

            pr, _, it = jax.lax.while_loop(
                cond, step, (pr0, jnp.float32(jnp.inf), jnp.int32(0)))
            return pr, it

        out = _owner_value_route(sspec, g, n, axis, a2a, owner, rowlive,
                                 frontier_budget, impl)
        if tol is None:
            return out[None]
        pr, it = out
        return pr[None], it[None]

    in_specs = (P(axis),) + ((P(axis),) if warm else ())
    out_specs = P(axis) if tol is None else (P(axis), P(axis))
    sharded = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                            out_specs=out_specs, check_vma=False)
    return sharded


def make_wcc(sspec: SortSpec, pspec: ep.PoolSpec, mesh, axis: str,
             m_cap: int, max_iters: int = 64,
             frontier_budget: Optional[int] = None, warm: bool = False):
    """Build ``wcc(state) -> uint32[n_shards, n_cap]`` — distributed weakly
    connected components by min-label propagation. Labels are CANONICAL
    across shard counts: each component converges to the minimum live vertex
    ID in it (the single-shard ``analytics.wcc`` reference uses row offsets;
    compare after mapping its labels to per-component min IDs). Requires a
    <= 32-bit ID universe (keys' hi word zero) so a label is one uint32 —
    every graph path in this repo packs 32-bit IDs. Assumes symmetric
    (undirected) edge insertion like the reference. Run on a vertex-synced
    state; 0xFFFFFFFF marks dead rows.

    Per round each shard pulls the min label over its LOCAL edges, then
    every live row's label rides the owner exchange: owners merge with a
    min-scatter and the merged label is broadcast back over the inverse
    all_to_all, so every copy of a vertex re-enters the next round with the
    global value. Terminates when no OWNER row improved (exact: copies are
    equal at round start, so any improvement lowers the owner's min).

    ``warm`` adds a ``(n_shards, n_cap)`` uint32 label seed (a previous
    epoch's output verbatim — UMAX at then-dead rows is the identity under
    min) and returns ``(labels, iters_run)``. Insert-only deltas only merge
    components, so prev labels are still valid upper bounds and propagation
    reaches the same min-ID fixed point in far fewer rounds."""
    n = int(mesh.shape[axis])
    UMAX = jnp.uint32(0xFFFFFFFF)

    def body(state, *extra):
        g = jax.tree.map(lambda x: x[0], state)
        n_cap = g.vt.del_time.shape[0]
        snap = rg.step_snapshot(sspec, pspec, m_cap, g, None)
        src, ok_e, dst = alg.csr_edges(snap)
        srcc = jnp.clip(src, 0, n_cap - 1)
        my, rowlive, owner, mine = _row_meta(sspec, g, n, axis)
        a2a = functools.partial(jax.lax.all_to_all, axis_name=axis,
                                split_axis=0, concat_axis=0)
        lab0 = jnp.where(rowlive, g.vt.ids[:, 1], UMAX)
        if warm:
            lab0 = jnp.where(rowlive, jnp.minimum(lab0, extra[0][0]), UMAX)

        def impl(rtgt, fwd, bwd):
            def cond(c):
                _, changed, it = c
                return changed & (it < max_iters)

            def step(c):
                lab, _, it = c
                cand = jnp.where(ok_e, lab[srcc], UMAX)
                pull = jnp.full((n_cap + 1,), UMAX, jnp.uint32).at[
                    dst].min(cand)
                nl = jnp.minimum(lab, pull[:n_cap])
                merged = jnp.full((n_cap + 1, 1), UMAX, jnp.uint32).at[
                    rtgt].min(fwd(nl[:, None]))
                back, okb = bwd(merged)
                nl = jnp.where(okb, back[:, 0], nl)
                ch = jax.lax.psum(jnp.any(mine & (nl < lab)).astype(
                    jnp.int32), axis) > 0
                return nl, ch, it + 1

            lab, _, it = jax.lax.while_loop(
                cond, step, (lab0, jnp.bool_(True), jnp.int32(0)))
            return lab, it

        lab, it = _owner_value_route(sspec, g, n, axis, a2a, owner, rowlive,
                                     frontier_budget, impl)
        out = jnp.where(rowlive, lab, UMAX)[None]
        return (out, it[None]) if warm else out

    in_specs = (P(axis),) + ((P(axis),) if warm else ())
    out_specs = (P(axis), P(axis)) if warm else P(axis)
    sharded = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                            out_specs=out_specs, check_vma=False)
    return sharded


def make_sssp(sspec: SortSpec, pspec: ep.PoolSpec, mesh, axis: str,
              m_cap: int, max_iters: int = 64,
              frontier_budget: Optional[int] = None, warm: bool = False):
    """Build ``sssp(state, source_key) -> float32[n_shards, n_cap]`` —
    distributed Bellman-Ford (non-negative weights). Per round each shard
    relaxes its LOCAL edges (``min(dist[u] + w)`` — the same float op the
    single-shard reference applies), owners merge candidates with a
    min-scatter, and the merged distance is broadcast back to every copy.
    min is exact in floating point and the edge set is partitioned, so the
    per-round distances — and the round count — are BIT-EXACT against
    ``analytics.sssp``. Run on a vertex-synced state; INF = unreachable.

    ``warm`` adds a ``(n_shards, n_cap)`` float32 distance seed (a previous
    epoch's output verbatim — INF at then-dead rows) and returns
    ``(dist, iters_run)``. Valid for insert / weight-decrease deltas only
    (prev distances stay upper bounds); the min-relax fixed point is
    schedule-independent, so the warm run converges to the scratch answer."""
    n = int(mesh.shape[axis])
    INF = alg.INF

    def body(state, source_key, *extra):
        g = jax.tree.map(lambda x: x[0], state)
        n_cap = g.vt.del_time.shape[0]
        snap = rg.step_snapshot(sspec, pspec, m_cap, g, None)
        src, ok_e, dst = alg.csr_edges(snap)
        srcc = jnp.clip(src, 0, n_cap - 1)
        w_e = jnp.where(ok_e, snap.weight, 0.0)
        my, rowlive, owner, mine = _row_meta(sspec, g, n, axis)
        a2a = functools.partial(jax.lax.all_to_all, axis_name=axis,
                                split_axis=0, concat_axis=0)

        off0 = sort_mod.lookup(sspec, g.sort, source_key[None, :])[0]
        row = jnp.arange(n_cap, dtype=jnp.int32)
        dist0 = jnp.where((row == off0) & rowlive, 0.0, INF)
        if warm:
            dist0 = jnp.where(rowlive, jnp.minimum(dist0, extra[0][0]), INF)

        def impl(rtgt, fwd, bwd):
            def cond(c):
                _, changed, it = c
                return changed & (it < max_iters)

            def step(c):
                dist, _, it = c
                cand = jnp.where(ok_e, dist[srcc] + w_e, INF)
                relax = jnp.full((n_cap + 1,), INF).at[dst].min(cand)
                nd = jnp.minimum(dist, relax[:n_cap])
                merged = jnp.full((n_cap + 1, 1), INF).at[rtgt].min(
                    fwd(nd[:, None]))
                back, okb = bwd(merged)
                nd = jnp.where(okb, back[:, 0], nd)
                ch = jax.lax.psum(jnp.any(mine & (nd < dist)).astype(
                    jnp.int32), axis) > 0
                return nd, ch, it + 1

            dist, _, it = jax.lax.while_loop(
                cond, step, (dist0, jnp.bool_(True), jnp.int32(0)))
            return dist, it

        dist, it = _owner_value_route(sspec, g, n, axis, a2a, owner,
                                      rowlive, frontier_budget, impl)
        out = jnp.where(rowlive, dist, INF)[None]
        return (out, it[None]) if warm else out

    in_specs = (P(axis), P()) + ((P(axis),) if warm else ())
    out_specs = (P(axis), P(axis)) if warm else P(axis)
    sharded = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                            out_specs=out_specs, check_vma=False)
    return sharded


def make_bfs_warm(sspec: SortSpec, pspec: ep.PoolSpec, mesh, axis: str,
                  m_cap: int, max_iters: int = 32,
                  frontier_budget: Optional[int] = None):
    """Build ``bfs_warm(state, source_key, warm) -> (int32[n_shards,
    n_cap], iters)`` — distributed BFS as integer min-plus relaxation
    seeded from a previous epoch's depths (``-1`` = unknown). Unlike the
    level-synchronous ``make_bfs`` this converges from ANY upper-bound
    seed: prev depths are upper bounds after an insert-only delta, the
    min-relax fixed point is the true BFS distance, and depths beyond
    ``max_iters`` mask to -1 exactly like the scratch program's level cap.
    Stub-row depths are authoritative here too (the owner broadcast runs
    every round), so parity vs scratch holds at owner rows."""
    n = int(mesh.shape[axis])
    BIG = jnp.int32(1 << 30)

    def body(state, source_key, warm_vals):
        g = jax.tree.map(lambda x: x[0], state)
        n_cap = g.vt.del_time.shape[0]
        snap = rg.step_snapshot(sspec, pspec, m_cap, g, None)
        src, ok_e, dst = alg.csr_edges(snap)
        srcc = jnp.clip(src, 0, n_cap - 1)
        my, rowlive, owner, mine = _row_meta(sspec, g, n, axis)
        a2a = functools.partial(jax.lax.all_to_all, axis_name=axis,
                                split_axis=0, concat_axis=0)

        off0 = sort_mod.lookup(sspec, g.sort, source_key[None, :])[0]
        row = jnp.arange(n_cap, dtype=jnp.int32)
        w0 = warm_vals[0]
        d0 = jnp.where(rowlive & (w0 >= 0), w0, BIG)
        d0 = jnp.where((row == off0) & rowlive, 0, d0)

        def impl(rtgt, fwd, bwd):
            def cond(c):
                _, changed, it = c
                return changed & (it < 2 * max_iters + 2)

            def step(c):
                d, _, it = c
                cand = jnp.where(ok_e, jnp.minimum(d[srcc], BIG) + 1, BIG)
                relax = jnp.full((n_cap + 1,), BIG, jnp.int32).at[
                    dst].min(cand)
                nd = jnp.minimum(d, relax[:n_cap])
                merged = jnp.full((n_cap + 1, 1), BIG, jnp.int32).at[
                    rtgt].min(fwd(nd[:, None]))
                back, okb = bwd(merged)
                nd = jnp.where(okb, back[:, 0], nd)
                ch = jax.lax.psum(jnp.any(mine & (nd < d)).astype(
                    jnp.int32), axis) > 0
                return nd, ch, it + 1

            d, _, it = jax.lax.while_loop(
                cond, step, (d0, jnp.bool_(True), jnp.int32(0)))
            return d, it

        d, it = _owner_value_route(sspec, g, n, axis, a2a, owner, rowlive,
                                   frontier_budget, impl)
        out = jnp.where(rowlive & (d <= max_iters), d, -1)[None]
        return out, it[None]

    sharded = jax.shard_map(body, mesh=mesh, in_specs=(P(axis), P(), P(axis)),
                            out_specs=(P(axis), P(axis)), check_vma=False)
    return sharded


def make_degree_map(sspec: SortSpec, pspec: ep.PoolSpec, mesh, axis: str,
                    m_cap: int):
    """Build ``deg(state) -> int32[n_shards, n_cap]`` — live out-degree at
    owner rows. Edges live in the source vertex's hash-owner shard (stub
    rows carry no adjacency), so the local CSR indptr diff at owner rows IS
    the full degree — no exchange needed."""
    n = int(mesh.shape[axis])

    def body(state):
        g = jax.tree.map(lambda x: x[0], state)
        snap = rg.step_snapshot(sspec, pspec, m_cap, g, None)
        my, rowlive, owner, mine = _row_meta(sspec, g, n, axis)
        deg = snap.indptr[1:] - snap.indptr[:-1]
        return jnp.where(mine, deg, 0)[None]

    sharded = jax.shard_map(body, mesh=mesh, in_specs=(P(axis),),
                            out_specs=P(axis), check_vma=False)
    return sharded


def make_num_edges(sspec: SortSpec, pspec: ep.PoolSpec, mesh, axis: str,
                   m_cap: int):
    """Build ``m(state) -> int32[n_shards]`` — per-shard live-edge
    partials; the store sums them host-side (scalar-result contract)."""
    def body(state):
        g = jax.tree.map(lambda x: x[0], state)
        snap = rg.step_snapshot(sspec, pspec, m_cap, g, None)
        return snap.m.astype(jnp.int32)[None]

    sharded = jax.shard_map(body, mesh=mesh, in_specs=(P(axis),),
                            out_specs=P(axis), check_vma=False)
    return sharded


def make_bc(sspec: SortSpec, pspec: ep.PoolSpec, mesh, axis: str,
            m_cap: int, max_depth: int = 32,
            frontier_budget: Optional[int] = None):
    """Build ``bc(state, source_keys) -> float32[n_shards, n_cap]`` —
    distributed Brandes betweenness (unweighted, sampled sources; the
    distributed analogue of ``analytics.bc``). All sources run TOGETHER:
    depth/sigma/delta carry an S column per source, so each forward level /
    backward level is one value exchange regardless of S.

    Forward (per level): shards accumulate path counts along local edges,
    owners sum the per-shard partials, mark newly-reached rows, and
    broadcast (depth, sigma) back to every copy. Backward (per level):
    dependency contributions accumulate at local SOURCE rows (edges live in
    the source row's shard), owners sum, and delta is broadcast back.
    Owner-side sums add per-shard partials in slot order — deterministic,
    but a different association than the single-shard segment-sum, so
    compare with a small float tolerance (depths are exact)."""
    n = int(mesh.shape[axis])

    def body(state, source_keys):
        g = jax.tree.map(lambda x: x[0], state)
        n_cap = g.vt.del_time.shape[0]
        S = source_keys.shape[0]
        snap = rg.step_snapshot(sspec, pspec, m_cap, g, None)
        src, ok_e, dst = alg.csr_edges(snap)
        srcc = jnp.clip(src, 0, n_cap - 1)
        dstc = jnp.clip(dst, 0, n_cap - 1)
        my, rowlive, owner, mine = _row_meta(sspec, g, n, axis)
        a2a = functools.partial(jax.lax.all_to_all, axis_name=axis,
                                split_axis=0, concat_axis=0)

        roffs = sort_mod.lookup(sspec, g.sort, source_keys)        # (S,)
        row = jnp.arange(n_cap, dtype=jnp.int32)
        is_src = (row[:, None] == roffs[None, :]) & (roffs[None, :] >= 0) \
            & rowlive[:, None]

        def impl(rtgt, fwd, bwd):
            depth0 = jnp.where(is_src, 0, -1)
            sigma0 = jnp.where(is_src, 1.0, 0.0)

            def sync_cols(vals):
                """Owner rows -> every copy (values already merged)."""
                back, okb = bwd(jnp.concatenate(
                    [vals, jnp.zeros((1, vals.shape[1]), vals.dtype)]))
                return back, okb

            def fwd_lvl(i, c):
                depth, sigma = c
                on_lvl = depth[srcc] == i
                add_l = jnp.zeros((n_cap + 1, S)).at[dst].add(
                    jnp.where(ok_e[:, None] & on_lvl,
                              sigma[srcc], 0.0))[:n_cap]
                add = jnp.zeros((n_cap + 1, S)).at[rtgt].add(
                    fwd(add_l))[:n_cap]
                newly = (add > 0) & (depth < 0)
                depth = jnp.where(newly, i + 1, depth)
                sigma = jnp.where(depth == i + 1, sigma + add, sigma)
                back, okb = sync_cols(jnp.concatenate(
                    [depth.astype(jnp.float32), sigma], axis=1))
                depth = jnp.where(okb[:, None],
                                  back[:, :S].astype(jnp.int32), depth)
                sigma = jnp.where(okb[:, None], back[:, S:], sigma)
                return depth, sigma

            depth, sigma = jax.lax.fori_loop(0, max_depth, fwd_lvl,
                                             (depth0, sigma0))

            du = depth[srcc]
            dv = depth[dstc]
            sig_ratio = sigma[srcc] / jnp.maximum(sigma[dstc], 1.0)

            def bwd_lvl(k, delta):
                lvl = max_depth - 1 - k
                onedge = ok_e[:, None] & (du == lvl) & (dv == lvl + 1)
                contrib = jnp.where(onedge,
                                    sig_ratio * (1.0 + delta[dstc]), 0.0)
                acc_l = jnp.zeros((n_cap, S)).at[srcc].add(contrib)
                acc = jnp.zeros((n_cap + 1, S)).at[rtgt].add(
                    fwd(acc_l))[:n_cap]
                delta = delta + acc
                back, okb = sync_cols(delta)
                return jnp.where(okb[:, None], back, delta)

            delta = jax.lax.fori_loop(0, max_depth, bwd_lvl,
                                      jnp.zeros((n_cap, S)))
            delta = jnp.where(is_src, 0.0, delta)
            return jnp.sum(delta, axis=1)

        vals = _owner_value_route(sspec, g, n, axis, a2a, owner, rowlive,
                                  frontier_budget, impl)
        return jnp.where(mine, vals, 0.0)[None]

    sharded = jax.shard_map(body, mesh=mesh, in_specs=(P(axis), P()),
                            out_specs=P(axis), check_vma=False)
    return sharded


def collect_owner_values(state: GraphState, values, n_shards: int) -> dict:
    """Host-side merge of a distributed analytics result: per-shard owner-row
    ``values`` (shape [n_shards, n_cap]) -> {vertex_id: value} over every
    live vertex (each vertex read from its single owner row). Vectorized —
    one mask + one zip, no per-row Python loop."""
    import numpy as np
    ids = np.asarray(state.vt.ids)
    dt = np.asarray(state.vt.del_time)
    vals = np.asarray(values)
    owner = np.asarray(shard_of_keys(
        jnp.asarray(ids.reshape(-1, 2)), n_shards)).reshape(ids.shape[:2])
    mask = (dt == 0) & (owner == np.arange(ids.shape[0])[:, None])
    vids = (ids[..., 0].astype(np.uint64) << np.uint64(32)) | \
        ids[..., 1].astype(np.uint64)
    return dict(zip(vids[mask].tolist(), vals[mask].tolist()))
