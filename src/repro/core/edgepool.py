"""Snapshot-log edge storage (paper §3.3) — functional, pool-based.

TPU adaptation: per-vertex ``malloc``'d edge arrays become contiguous block
**extents** inside one global pool, so

* append = vectorized scatter at ``start_block*BS + size + rank`` (the batched
  analogue of the paper's lock-free ``fetch_add`` slot claim),
* the snapshot/log split is positional: entries [0, deg) are the snapshot,
  [deg, size) the log; capacity keeps the paper's ``cap = 2·snapshot``
  discipline so compaction stays amortized O(1) per op (Theorem 2),
* compaction (Alg. 2) runs batched over up to K_MAX overflowing vertices with
  the duplicate-checker kernel; larger events fall through to a global
  **defragmentation** — a fully-vectorized rebuild (sort + cumsum re-layout)
  that doubles as the allocator's garbage collector. Bump allocation between
  defrags replaces free lists (TPUs want bulk re-layout, not pointer reuse).
  Rows at or past ``next_block`` are always empty (dst -1, weight 0, ts 0):
  fresh pools and rebuild images start empty and every write lands below
  ``next_block``, so newly allocated extents need no initializing writes.
* every entry carries a timestamp; reads at ``read_ts`` give MVCC snapshot
  semantics (paper §3.3 "Version management" — old functional states are the
  versioned arrays).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .vertex_table import VertexTable
from repro.kernels import ops as kops

__all__ = ["EdgePool", "PoolSpec", "make_edge_pool", "apply_edge_updates",
           "get_neighbors", "live_edges", "defrag"]

INT_MAX = np.int32(0x7FFFFFFF)


@dataclass(frozen=True)
class PoolSpec:
    n_blocks: int          # total blocks in the pool
    block_size: int = 16   # entries per block (lane-friendly)
    k_max: int = 256       # max per-batch vertex compactions (fast path)
    dmax: int = 4096       # max edge-array entries handled by the fast path
    # live-edge probe window (ingest fast path): the pre-append pair-liveness
    # probe gathers at most ``probe_width`` entries per DISTINCT touched pair
    # instead of a dense (B, dmax) slab; vertices whose arrays outgrow the
    # window flag the counter dirty unless this batch's compaction already
    # touched them (their liveness folds out of the compaction gather free).
    probe_width: int = 256
    # two-tier fast-path compaction: up to ``k_max`` overflowing vertices
    # whose arrays fit the probe window compact at window width (the common
    # allocation/growth case), and up to ``k_big`` wider ones (≤ dmax) pay
    # the full-width gather — so per-batch compaction cost tracks the small
    # tier, not k_max × dmax. A batch overflowing MORE than k_big big
    # vertices falls back to a defrag (correct, amortized by the 2x capacity
    # growth: a given vertex overflows O(log d) times total); hub-heavy
    # streams that hit this repeatedly should raise k_big — each unit costs
    # one extra dmax-width compaction row per batch.
    k_big: int = 16
    append_impl: str = "auto"   # 'ref' (jnp scatter) | 'pallas' fused kernel
    compact_impl: str = "auto"
    # global-rebuild strategy: 'stream' (default) runs the block-row
    # streaming rebuild — size-segmented per-vertex row compaction
    # (kernels defrag_rows) + whole-block extent writes, falling back to
    # the dense entry-scatter rebuild whenever a size segment overflows
    # its static budget or a vertex outgrew dmax; 'dense' forces the old
    # full-pool lexsort rebuild (the bit-exact reference, kept for the
    # parity property tests and before/after benchmarks).
    defrag_impl: str = "auto"   # 'auto'/'stream' | 'dense'
    # edge-storage policy (baseline paradigms on the same substrate):
    #  'snaplog' — the paper: dedup compaction, log segment = snapshot size
    #  'grow'    — log-structured (LiveGraph/GTX-style): no dedup, double cap
    #  'sorted'  — Spruce-style: dedup + sort by dst, fixed small buffer
    policy: str = "snaplog"
    buf_blocks: int = 1    # 'sorted' policy: log buffer size (blocks)

    @property
    def capacity_entries(self) -> int:
        return self.n_blocks * self.block_size


class EdgePool(NamedTuple):
    dst: jnp.ndarray       # int32[n_blocks, BS] destination OFFSETS (edge chain); -1 empty
    weight: jnp.ndarray    # float32[n_blocks, BS]; 0.0 = NULL tombstone
    ts: jnp.ndarray        # int32[n_blocks, BS]
    owner: jnp.ndarray     # int32[n_blocks] owning vertex offset, -1 free
    next_block: jnp.ndarray  # int32 scalar bump allocator
    garbage: jnp.ndarray   # int32 scalar — stale entries since last defrag
    clock: jnp.ndarray     # int32 scalar — global timestamp
    overflow: jnp.ndarray  # int32 scalar — pool-exhaustion events
    live_m: jnp.ndarray    # int32 scalar — live (deduped, tombstone-free) edges
    live_dirty: jnp.ndarray  # int32 scalar — 1 when live_m needs a recount
    defrags: jnp.ndarray   # int32 scalar — global rebuilds so far (hub-heavy
    #                        streams exceeding k_big per batch show up here)
    tiles_scanned: jnp.ndarray  # int32 scalar — cumulative pool tiles the
    #                        bounded append visits (touched extents + landed
    #                        slots per batch, NOT tiles x batches: the
    #                        counter certifies the prefetched scan bound)


def make_edge_pool(spec: PoolSpec) -> EdgePool:
    nb, bs = spec.n_blocks, spec.block_size
    z = jnp.zeros((), jnp.int32)
    return EdgePool(
        dst=jnp.full((nb, bs), -1, jnp.int32),
        weight=jnp.zeros((nb, bs), jnp.float32),
        ts=jnp.zeros((nb, bs), jnp.int32),
        owner=jnp.full((nb,), -1, jnp.int32),
        next_block=z, garbage=z, clock=jnp.ones((), jnp.int32), overflow=z,
        live_m=z, live_dirty=z, defrags=z, tiles_scanned=z,
    )


# --------------------------------------------------------------------------
# helpers
# --------------------------------------------------------------------------

def _cdiv(a, b):
    return (a + b - 1) // b


def _group_by(u: jnp.ndarray, valid: jnp.ndarray):
    """Stable-sort ops by target vertex. Returns dict with the sorted view."""
    B = u.shape[0]
    key = jnp.where(valid, u, INT_MAX)
    order = jnp.argsort(key, stable=True)
    su = key[order]
    prev = jnp.concatenate([jnp.full((1,), -1, su.dtype), su[:-1]])
    first = (su != prev) & (su < INT_MAX)
    gid = jnp.cumsum(first.astype(jnp.int32)) - 1  # group index per sorted op
    # start index of each op's group:
    idx = jnp.arange(B, dtype=jnp.int32)
    start_of_group = jnp.where(first, idx, 0)
    start = jax.lax.cummax(start_of_group)
    rank = idx - start
    # per-group info at group slots (positions 0..ng-1):
    gstart = jnp.nonzero(first, size=B, fill_value=B)[0].astype(jnp.int32)
    gu = su[jnp.clip(gstart, 0, B - 1)]
    nxt = jnp.concatenate([gstart[1:], jnp.full((1,), B, jnp.int32)])
    # count = next group start - start, but next fill is B and invalid groups
    # must count 0:
    ng = jnp.sum(first.astype(jnp.int32))
    garange = jnp.arange(B, dtype=jnp.int32)
    gvalid = garange < ng
    # total valid ops:
    nvalid = jnp.sum(valid.astype(jnp.int32))
    gend = jnp.where(garange + 1 < ng, nxt, nvalid)
    gcount = jnp.where(gvalid, gend - gstart, 0)
    return dict(order=order, su=su, gid=gid, rank=rank, gstart=gstart, gu=gu,
                gcount=gcount, gvalid=gvalid, ng=ng)


def _gather_vertex_entries(spec: PoolSpec, pool: EdgePool, vt: VertexTable,
                           u: jnp.ndarray, width: int):
    """Gather up to ``width`` occupied entries of each vertex in ``u``.

    Returns (dst, w, ts) of shape (K, width) plus validity handled via size.
    """
    K = u.shape[0]
    bs = spec.block_size
    uc = jnp.clip(u, 0, vt.size.shape[0] - 1)
    start = vt.start_block[uc]
    size = jnp.where(u >= 0, vt.size[uc], 0)
    e = jnp.arange(width, dtype=jnp.int32)[None, :]
    blk = start[:, None] + e // bs
    lane = e % bs
    ok = (e < size[:, None]) & (start[:, None] >= 0)
    blk = jnp.where(ok, blk, 0)
    d = jnp.where(ok, pool.dst[blk, lane], -1)
    w = jnp.where(ok, pool.weight[blk, lane], 0.0)
    t = jnp.where(ok, pool.ts[blk, lane], 0)
    return d, w, t, size


def _scatter_entries(pool: EdgePool, tgt_block, lane, valid, d, w, t):
    """Land every valid op's (d, w, t) at its (block, lane) slot.

    Slots are distinct but several ops can share a block row, so the ops
    are merged per row in a (B, bs) staging buffer and each touched row is
    written back whole, once. A row scatter updates the pool in place on a
    TPU; an element scatter into the (nb, bs) pool relays the whole pool
    out and back on every batch."""
    nb = pool.dst.shape[0]
    tb = jnp.where(valid, tgt_block, nb)
    order = jnp.argsort(tb)
    stb = tb[order]
    head = jnp.concatenate([jnp.ones((1,), bool), stb[1:] != stb[:-1]])
    row = jnp.cumsum(head.astype(jnp.int32)) - 1     # staging row per op
    row_blk = jnp.full(tb.shape, nb, jnp.int32).at[row].set(stb)
    src = jnp.clip(row_blk, 0, nb - 1)
    sl = lane[order]

    def land(col, val):
        staged = col[src].at[row, sl].set(val[order])
        return col.at[row_blk].set(staged, mode="drop")

    return pool._replace(dst=land(pool.dst, d), weight=land(pool.weight, w),
                         ts=land(pool.ts, t))


# --------------------------------------------------------------------------
# first-touch extent allocation (fast path, whole batch)
# --------------------------------------------------------------------------

def _alloc_extents(spec: PoolSpec, pool: EdgePool, vt: VertexTable,
                   ku: jnp.ndarray, kmask: jnp.ndarray,
                   kincoming: jnp.ndarray):
    """Assign fresh extents to vertices with NO edge array yet (the mass
    first-touch case of every ingest stream). There is nothing to gather or
    dedup — the whole batch's allocations are laid out with one cumsum, and
    their rows (past ``next_block``) are already empty. The block budget is
    proportional to the BATCH (Σ blocks ≤ B/bs + Σ base_log), so thousands
    of new vertices per batch never spill into the compaction tiers or
    force a defrag."""
    bs = spec.block_size
    K = ku.shape[0]
    nb = pool.dst.shape[0]
    n_cap = vt.size.shape[0]
    base_log = spec.buf_blocks if spec.policy == "sorted" else 1

    new_blocks = jnp.where(kmask,
                           jnp.maximum(_cdiv(kincoming, bs), base_log), 0)
    total = jnp.sum(new_blocks)
    base = pool.next_block + jnp.cumsum(new_blocks) - new_blocks

    # flat row -> owning vertex mapping (interval search over the layout);
    # Σ new_blocks ≤ Σ(cdiv + base_log) ≤ K·(base_log+1) + B/bs, and the
    # budget doubles as a belt-and-braces overflow guard
    R_total = K * (base_log + 1) + _cdiv(K, bs)
    fits = (pool.next_block + total <= nb) & (total <= R_total)
    kmask = kmask & fits
    r = jnp.arange(R_total, dtype=jnp.int32)
    ends = jnp.cumsum(new_blocks)
    krow = jnp.searchsorted(ends, r, side="right").astype(jnp.int32)
    krc = jnp.clip(krow, 0, K - 1)
    valid_r = (r < total) & fits
    tgt_rows = jnp.where(valid_r, pool.next_block + r, nb)
    owner = pool.owner.at[tgt_rows].set(jnp.where(valid_r, ku[krc], -1),
                                        mode="drop")

    tgt = jnp.where(kmask, ku, n_cap)
    vt = vt._replace(
        cap=vt.cap.at[tgt].set(new_blocks * bs, mode="drop"),
        start_block=vt.start_block.at[tgt].set(
            jnp.where(new_blocks > 0, base, -1), mode="drop"),
    )
    pool = pool._replace(owner=owner,
                         next_block=pool.next_block +
                         jnp.where(fits, total, 0),
                         overflow=pool.overflow + jnp.where(fits, 0, 1))
    return pool, vt


# --------------------------------------------------------------------------
# per-vertex compaction (fast path) — paper Alg. 2 batched over K_MAX vertices
# --------------------------------------------------------------------------

def _fold_words(n_cap: int) -> int:
    return (n_cap + 31) // 32


def _scatter_block_rows(pool: EdgePool, tgt_rows, d_rows, w_rows, t_rows):
    """Write whole (bs,)-entry block rows: compaction targets are contiguous
    block-aligned extents, so one row-scatter replaces bs entry-scatters."""
    return pool._replace(
        dst=pool.dst.at[tgt_rows].set(d_rows, mode="drop"),
        weight=pool.weight.at[tgt_rows].set(w_rows, mode="drop"),
        ts=pool.ts.at[tgt_rows].set(t_rows, mode="drop"),
    )


def _compact_vertices(spec: PoolSpec, pool: EdgePool, vt: VertexTable,
                      ku: jnp.ndarray, kmask: jnp.ndarray,
                      kincoming: jnp.ndarray, width: int, fold: bool):
    """Compact + grow the edge arrays of vertices ``ku`` (masked), each with
    at most ``width`` occupied entries.

    New capacity (entries) = snapB + max(snapB, incomingB, 1) blocks where
    snapB = blocks(d') — the paper's "new array of capacity 2d, reserving d
    log entries", generalized so the pending batch always fits.

    Returns (pool, vt, fold_ku, fold_bitmap). With ``fold=True`` the deduped
    live set of each compacted vertex — already materialized by the (K,
    width) compaction gather — is returned as a per-vertex bitmap over the
    destination universe, so the live-edge probe stays exact for pairs whose
    owner outgrew the bounded probe window but was compacted this batch.
    ('grow' keeps duplicates and tombstones, so its fold is never valid.)
    """
    bs = spec.block_size
    K = ku.shape[0]
    n_cap = vt.size.shape[0]
    nb = pool.dst.shape[0]

    d0, w0, t0, size0 = _gather_vertex_entries(spec, pool, vt,
                                               jnp.where(kmask, ku, -1),
                                               width)
    if spec.policy == "grow":
        # log-structured baseline: copy everything, no dedup (reads pay O(log))
        cd, cw, ct, cnt = d0, w0, t0, size0
    else:
        cd, cw, ct, cnt = kops.compact_rows(d0, w0, t0, size0,
                                            impl=spec.compact_impl)
        if spec.policy == "sorted":
            # Spruce-style: snapshot kept sorted by destination
            D = cd.shape[1]
            pos = jnp.arange(D, dtype=jnp.int32)[None, :]
            skey = jnp.where(pos < cnt[:, None], cd, INT_MAX)
            o = jnp.argsort(skey, axis=-1, stable=True)
            cd = jnp.take_along_axis(cd, o, -1)
            cw = jnp.take_along_axis(cw, o, -1)
            ct = jnp.take_along_axis(ct, o, -1)
    cnt = jnp.where(kmask, cnt, 0)

    snap_blocks = _cdiv(cnt, bs)
    if spec.policy == "sorted":
        log_blocks = jnp.full_like(snap_blocks, spec.buf_blocks)
    else:  # 'snaplog' (paper: log = snapshot) and 'grow' (double capacity)
        log_blocks = jnp.maximum(jnp.maximum(snap_blocks, _cdiv(kincoming, bs)), 1)
    log_blocks = jnp.maximum(log_blocks, _cdiv(kincoming, bs))
    new_blocks = jnp.where(kmask, snap_blocks + log_blocks, 0)

    base = pool.next_block + jnp.cumsum(new_blocks) - new_blocks
    total = jnp.sum(new_blocks)
    fits = pool.next_block + total <= nb  # caller guarantees via defrag check
    kmask = kmask & fits

    # per-vertex liveness bitmap over dst offsets (fold for the live probe);
    # after dedup each dst appears once per row, so distinct bits per word
    # make scatter-add equivalent to scatter-OR
    Ww = _fold_words(n_cap)
    if fold and spec.policy != "grow":
        ee = jnp.arange(width, dtype=jnp.int32)[None, :]
        entry_ok = kmask[:, None] & (ee < cnt[:, None]) & (cd >= 0)
        cdc = jnp.clip(cd, 0, n_cap - 1)
        krow = jnp.broadcast_to(jnp.arange(K, dtype=jnp.int32)[:, None],
                                (K, width))
        word = jnp.where(entry_ok, cdc >> 5, Ww)
        bit = jnp.where(entry_ok,
                        jnp.uint32(1) << (cdc & 31).astype(jnp.uint32),
                        jnp.uint32(0))
        fold_bitmap = jnp.zeros((K, Ww), jnp.uint32).at[
            krow.reshape(-1), word.reshape(-1)].add(bit.reshape(-1),
                                                    mode="drop")
        fold_ku = jnp.where(kmask, ku, -1)
    else:
        fold_bitmap = jnp.zeros((K, Ww), jnp.uint32)
        fold_ku = jnp.full((K,), -1, jnp.int32)

    # ---- write the new extents' content as whole BLOCK ROWS (extents are
    # block-aligned, so a row scatter replaces bs entry scatters): the
    # compacted prefix padded with empties. The log rows out to the extent
    # end lie past ``next_block`` and are already empty. ``MB`` bounds any
    # extent this call can build: snapB <= R1 rows, logB <= max(blocks(dmax),
    # buf_blocks) rows (the caller defrags instead when a vertex's incoming
    # exceeds dmax).
    R1 = _cdiv(width, bs)
    padw = R1 * bs - width
    if padw:
        cd = jnp.pad(cd, ((0, 0), (0, padw)), constant_values=-1)
        cw = jnp.pad(cw, ((0, 0), (0, padw)))
        ct = jnp.pad(ct, ((0, 0), (0, padw)))
    e = jnp.arange(R1 * bs, dtype=jnp.int32)[None, :]
    fillm = e < cnt[:, None]
    rowi = jnp.arange(R1, dtype=jnp.int32)[None, :]
    row_ok = kmask[:, None] & (rowi < new_blocks[:, None])
    pool = _scatter_block_rows(
        pool, jnp.where(row_ok, base[:, None] + rowi, nb).reshape(-1),
        jnp.where(fillm, cd, -1).reshape(K * R1, bs),
        jnp.where(fillm, cw, 0.0).reshape(K * R1, bs),
        jnp.where(fillm, ct, 0).reshape(K * R1, bs))

    MB = R1 + max(_cdiv(spec.dmax, bs), spec.buf_blocks) + 1
    cap_entries = new_blocks * bs

    # ownership: new extents -> u ; old extents -> -1 (garbage)
    b = jnp.arange(MB, dtype=jnp.int32)[None, :]
    new_ob = jnp.where(kmask[:, None] & (b < new_blocks[:, None]),
                       base[:, None] + b, nb)
    ucast = jnp.broadcast_to(ku[:, None], (K, MB))
    owner = pool.owner.at[new_ob.reshape(-1)].set(ucast.reshape(-1), mode="drop")
    uc = jnp.clip(ku, 0, n_cap - 1)
    old_start = jnp.where(kmask, vt.start_block[uc], -1)
    old_blocks = jnp.where(kmask & (old_start >= 0), _cdiv(vt.cap[uc], bs), 0)
    old_ob = jnp.where(kmask[:, None] & (b < old_blocks[:, None]),
                       old_start[:, None] + b, nb)
    owner = owner.at[old_ob.reshape(-1)].set(-1, mode="drop")

    garbage = pool.garbage + jnp.sum(jnp.where(kmask, vt.size[uc], 0) - cnt)
    pool = pool._replace(owner=owner,
                         next_block=pool.next_block + jnp.where(fits, total, 0),
                         garbage=garbage,
                         overflow=pool.overflow + jnp.where(fits, 0, 1))

    # vertex table bookkeeping
    tgt = jnp.where(kmask, ku, n_cap)
    vt = vt._replace(
        deg=vt.deg.at[tgt].set(cnt, mode="drop"),
        size=vt.size.at[tgt].set(cnt, mode="drop"),
        cap=vt.cap.at[tgt].set(cap_entries, mode="drop"),
        start_block=vt.start_block.at[tgt].set(jnp.where(new_blocks > 0, base,
                                                         -1), mode="drop"),
    )
    return pool, vt, fold_ku, fold_bitmap


# --------------------------------------------------------------------------
# global defragmentation — streaming block-row rebuild, GC, vertex-offset
# recycling (dense entry-scatter rebuild kept as the bit-exact reference)
# --------------------------------------------------------------------------

def _rebuild_layout(spec: PoolSpec, vt: VertexTable, d_cnt: jnp.ndarray,
                    incoming: jnp.ndarray):
    """New extent layout of a rebuild: each live vertex with content (or
    pending ``incoming`` ops) gets ``cap = snapB + max(snapB, incomingB,
    1)`` blocks (2d discipline), laid out in vertex-row order."""
    bs = spec.block_size
    snapB = _cdiv(d_cnt, bs)
    has_any = (d_cnt > 0) | (incoming > 0)
    active_row = vt.del_time == 0
    if spec.policy == "sorted":
        base_logB = jnp.full_like(snapB, spec.buf_blocks)
    else:
        base_logB = jnp.maximum(snapB, 1)
    logB = jnp.where(active_row & has_any,
                     jnp.maximum(base_logB, _cdiv(incoming, bs)), 0)
    blocks = jnp.where(active_row, snapB + logB, 0)
    bstart = jnp.cumsum(blocks) - blocks
    return blocks, bstart, jnp.sum(blocks), active_row


def _rebuild_finalize(spec: PoolSpec, pool: EdgePool, vt: VertexTable,
                      new_dst, new_w, new_t, d_cnt, blocks, bstart,
                      total_blocks, live_cnt, active_row):
    """Shared rebuild tail: block ownership via interval mapping, deleted
    vertex rows recycled into the free ring (the paper's epoch-safe purge —
    offsets are only reused after the rebuild, so stale extent references
    cannot resurrect), vertex table + pool bookkeeping. The rebuild is the
    live counter's resynchronization point: ``live_m`` becomes exact and
    any dirtiness (vertex deletes, dropped ops) is healed here."""
    bs = spec.block_size
    nb = pool.dst.shape[0]
    n_cap = vt.size.shape[0]

    bidx = jnp.arange(nb, dtype=jnp.int32)
    vown = jnp.searchsorted(bstart + blocks, bidx, side="right").astype(jnp.int32)
    vownc = jnp.clip(vown, 0, n_cap - 1)
    inside = (bidx < total_blocks) & (bidx >= bstart[vownc]) & (blocks[vownc] > 0)
    new_owner = jnp.where(inside, vownc, -1)

    deleted = vt.del_time > 0
    del_idx = jnp.nonzero(deleted, size=n_cap, fill_value=n_cap)[0].astype(jnp.int32)
    n_del = jnp.sum(deleted.astype(jnp.int32))
    r = jnp.arange(n_cap, dtype=jnp.int32)
    q_pos = (vt.free_tail + r) % n_cap
    q_tgt = jnp.where(r < n_del, q_pos, n_cap)
    free_q = vt.free_q.at[q_tgt].set(del_idx, mode="drop")
    dtgt = jnp.where(deleted, r, n_cap)
    del_time = vt.del_time.at[dtgt].set(-1, mode="drop")

    vt = vt._replace(
        deg=jnp.where(active_row, d_cnt, 0),
        size=jnp.where(active_row, d_cnt, 0),
        cap=jnp.where(active_row, blocks * bs, 0),
        start_block=jnp.where(active_row & (blocks > 0), bstart, -1),
        free_q=free_q,
        free_tail=vt.free_tail + n_del,
        del_time=del_time,
    )
    pool = pool._replace(dst=new_dst, weight=new_w, ts=new_t, owner=new_owner,
                         next_block=total_blocks,
                         garbage=jnp.zeros((), jnp.int32),
                         live_m=live_cnt,
                         live_dirty=jnp.zeros((), jnp.int32),
                         defrags=pool.defrags + 1)
    return pool, vt


def _defrag_dense(spec: PoolSpec, pool: EdgePool, vt: VertexTable,
                  incoming: jnp.ndarray):
    """Dense rebuild reference: flatten every pool lane, one full-pool
    3-key lexsort, entry-level scatters. O(N log N) in the pool CAPACITY —
    the streaming rebuild below is the production path; this stays as the
    bit-exact semantic reference and the fallback for states the size
    segments cannot express (a vertex past dmax, segment overflow)."""
    bs = spec.block_size
    nb = pool.dst.shape[0]
    n_cap = vt.size.shape[0]
    N = nb * bs

    own = jnp.repeat(pool.owner, bs)
    d = pool.dst.reshape(-1)
    w = pool.weight.reshape(-1)
    t = pool.ts.reshape(-1)
    # entry liveness: within owner's occupied prefix
    blk_index = jnp.arange(N, dtype=jnp.int32) // bs
    lane = jnp.arange(N, dtype=jnp.int32) % bs
    ownc = jnp.clip(own, 0, n_cap - 1)
    start = vt.start_block[ownc]
    pos_in_extent = (blk_index - start) * bs + lane
    occupied = (own >= 0) & (pos_in_extent >= 0) & (pos_in_extent < vt.size[ownc])
    src_alive = vt.del_time[ownc] == 0
    dstc = jnp.clip(d, 0, n_cap - 1)
    dst_alive = (d >= 0) & (vt.del_time[dstc] == 0)
    valid = occupied & src_alive & dst_alive & (d >= 0)

    # ---- last-writer-wins on (owner, dst) by ts ----
    SENT = INT_MAX
    so = jnp.where(valid, own, SENT)
    sd = jnp.where(valid, d, SENT)
    stv = jnp.where(valid, t, 0)
    order = jnp.lexsort((stv, sd, so))
    so, sd, sw, stv = so[order], sd[order], w[order], stv[order]
    sval = so < SENT
    nxt_o = jnp.concatenate([so[1:], jnp.full((1,), -2, so.dtype)])
    nxt_d = jnp.concatenate([sd[1:], jnp.full((1,), -2, sd.dtype)])
    is_last = ((so != nxt_o) | (sd != nxt_d)) & sval
    # live pairs after the rebuild (exact, policy-independent): the defrag is
    # the counter's resynchronization point — ``live_m`` becomes exact and any
    # dirtiness (vertex deletes, dropped ops) is healed here
    live_cnt = jnp.sum((is_last & (sw != 0)).astype(jnp.int32))
    if spec.policy == "grow":
        keep = sval  # log-structured baseline: retain every version
    else:
        keep = is_last & (sw != 0)

    # ---- per-vertex live counts & new extents ----
    so_keep = jnp.where(keep, so, n_cap)
    d_cnt = jnp.zeros((n_cap,), jnp.int32).at[so_keep].add(1, mode="drop")
    blocks, bstart, total_blocks, active_row = _rebuild_layout(
        spec, vt, d_cnt, incoming)

    # ---- write entries into fresh arrays ----
    # rank of each kept entry within its owner = position among keeps with
    # same owner; entries are sorted by owner so rank = idx - first_keep_idx
    # rank via segmented cumsum of keep:
    keep_i = keep.astype(jnp.int32)
    csum = jnp.cumsum(keep_i)
    owner_change = jnp.concatenate([jnp.ones((1,), bool), so[1:] != so[:-1]])
    seg_base = jax.lax.cummax(jnp.where(owner_change, csum - keep_i, 0))
    rank = csum - 1 - seg_base

    soc = jnp.clip(so, 0, n_cap - 1)
    entry_pos = bstart[soc] * bs + rank
    tgt_blk = jnp.where(keep, entry_pos // bs, nb)
    tgt_lane = entry_pos % bs

    new_dst = jnp.full((nb, bs), -1, jnp.int32).at[tgt_blk, tgt_lane].set(
        sd, mode="drop")
    new_w = jnp.zeros((nb, bs), jnp.float32).at[tgt_blk, tgt_lane].set(
        sw, mode="drop")
    new_t = jnp.zeros((nb, bs), jnp.int32).at[tgt_blk, tgt_lane].set(
        stv, mode="drop")

    return _rebuild_finalize(spec, pool, vt, new_dst, new_w, new_t, d_cnt,
                             blocks, bstart, total_blocks, live_cnt,
                             active_row)


def _defrag_tiers(spec: PoolSpec, n_cap: int):
    """Static (width, budget) size segments of the streaming rebuild:
    widths grow 8x from one block up to dmax; budgets shrink 8x from the
    full vertex table (heavy-tailed degree distributions put almost every
    vertex in the first segment), floored so hub-heavy states — up to
    4*k_big over-window vertices — still stream. A segment whose live
    population exceeds its budget falls back to the dense rebuild, so the
    budgets trade streaming coverage for bounded gather shapes."""
    bs = spec.block_size
    top = max(_cdiv(spec.dmax, bs) * bs, bs)
    tiers = []
    w, j = bs, 0
    while True:
        w = min(w, top)
        tiers.append((w, min(n_cap, max(64, 4 * spec.k_big,
                                        n_cap >> (3 * j)))))
        if w >= top:
            break
        w, j = w * 8, j + 1
    return tiers


def _defrag_chunks(width: int, budget: int):
    """Geometric chunk schedule of one size segment: (start, rows) pieces
    doubling from ~64K gathered entries, so a segment costs O(population)
    work at runtime — each chunk is skipped by a ``lax.cond`` unless the
    segment's population reaches its start."""
    c = max(32, min(budget, 65536 // max(width, 1)))
    chunks, lo = [], 0
    while lo < budget:
        c = min(c, budget - lo)
        chunks.append((lo, c))
        lo += c
        c *= 2
    return chunks


def _defrag_stream(spec: PoolSpec, pool: EdgePool, vt: VertexTable,
                   incoming: jnp.ndarray, tiers, tier_masks):
    """Block-row streaming rebuild: per size segment, gather each live
    vertex's extent once, run the ``defrag_rows`` row compactor (dedup +
    tombstone/dead-dst drop + dst-ascending emission), and write the new
    extents as whole block rows into a fresh pool image. Segments are
    processed in geometrically-growing chunks, each behind a ``lax.cond``
    on the segment's population, so runtime work is proportional to the
    extents that actually exist (within 2x), never the static budgets or
    the pool capacity — and nothing is ever sorted across vertices: the
    extent layout already IS the owner order. Bit-exact vs
    ``_defrag_dense`` (asserted by the parity property test)."""
    bs = spec.block_size
    nb = pool.dst.shape[0]
    n_cap = vt.size.shape[0]
    keep_all = spec.policy == "grow"
    dead_dst = vt.del_time != 0

    d_cnt = jnp.zeros((n_cap,), jnp.int32)
    live_cnt = jnp.zeros((), jnp.int32)
    parts = []
    for (W, Bj), mask in zip(tiers, tier_masks):
        pop = jnp.sum(mask.astype(jnp.int32))
        kidx = jnp.nonzero(mask, size=Bj, fill_value=n_cap)[0].astype(
            jnp.int32)
        for lo, C in _defrag_chunks(W, Bj):
            kidx_c = jax.lax.slice(kidx, (lo,), (lo + C,))

            def compact_chunk(carry, kidx_c=kidx_c, W=W):
                d_cnt, live_cnt = carry
                kmask = kidx_c < n_cap
                ku = jnp.where(kmask, kidx_c, -1)
                d0, w0, t0, ksz = _gather_vertex_entries(spec, pool, vt,
                                                         ku, W)
                # edges to deleted vertices drop like the dense rebuild
                dd = jnp.where((d0 >= 0) &
                               dead_dst[jnp.clip(d0, 0, n_cap - 1)],
                               -1, d0)
                cd, cw, ct, cnt, liv = kops.defrag_rows(
                    dd, w0, t0, ksz, keep_all=keep_all,
                    impl=spec.compact_impl)
                cnt = jnp.where(kmask, cnt, 0)
                d_cnt = d_cnt.at[jnp.where(kmask, ku, n_cap)].set(
                    cnt, mode="drop")
                live_cnt = live_cnt + jnp.sum(jnp.where(kmask, liv, 0))
                return (d_cnt, live_cnt), (ku, cd, cw, ct, cnt)

            def skip_chunk(carry, C=C, W=W):
                return carry, (jnp.full((C,), -1, jnp.int32),
                               jnp.full((C, W), -1, jnp.int32),
                               jnp.zeros((C, W), jnp.float32),
                               jnp.zeros((C, W), jnp.int32),
                               jnp.zeros((C,), jnp.int32))

            run = pop > lo
            (d_cnt, live_cnt), part = jax.lax.cond(
                run, compact_chunk, skip_chunk, (d_cnt, live_cnt))
            parts.append((run, W, part))

    blocks, bstart, total_blocks, active_row = _rebuild_layout(
        spec, vt, d_cnt, incoming)

    # fresh image: only content rows are ever written (block-row moves
    # bounded by the live snapshot); log rows stay at the empty fill
    img = pool._replace(dst=jnp.full((nb, bs), -1, jnp.int32),
                        weight=jnp.zeros((nb, bs), jnp.float32),
                        ts=jnp.zeros((nb, bs), jnp.int32))
    for run, W, (ku, cd, cw, ct, cnt) in parts:
        R = W // bs
        K = ku.shape[0]

        def write_chunk(im, ku=ku, cd=cd, cw=cw, ct=ct, cnt=cnt, R=R, K=K):
            base = bstart[jnp.clip(ku, 0, n_cap - 1)]
            rowi = jnp.arange(R, dtype=jnp.int32)[None, :]
            row_ok = (ku >= 0)[:, None] & (rowi < _cdiv(cnt, bs)[:, None])
            return _scatter_block_rows(
                im, jnp.where(row_ok, base[:, None] + rowi, nb).reshape(-1),
                cd.reshape(K * R, bs), cw.reshape(K * R, bs),
                ct.reshape(K * R, bs))

        img = jax.lax.cond(run, write_chunk, lambda im: im, img)

    return _rebuild_finalize(spec, pool, vt, img.dst, img.weight, img.ts,
                             d_cnt, blocks, bstart, total_blocks, live_cnt,
                             active_row)


def defrag_segments(spec: PoolSpec, vt: VertexTable):
    """The streaming rebuild's size segments for ``vt``'s live extents:
    ``(tiers, masks, stream_ok)``. ``stream_ok`` holds when every extent
    fits a segment within its budget; otherwise ``defrag`` takes the
    dense path."""
    tiers = _defrag_tiers(spec, vt.size.shape[0])
    live_row = (vt.del_time == 0) & (vt.start_block >= 0)
    sz = jnp.where(live_row, vt.size, 0)
    masks, fits = [], []
    prev = 0
    for W, Bj in tiers:
        m = live_row & (sz > prev) & (sz <= W)
        masks.append(m)
        fits.append(jnp.sum(m.astype(jnp.int32)) <= Bj)
        prev = W
    stream_ok = jnp.all(jnp.stack(fits)) & (jnp.max(sz) <= tiers[-1][0])
    return tiers, masks, stream_ok


def defrag(spec: PoolSpec, pool: EdgePool, vt: VertexTable,
           incoming: jnp.ndarray | None = None):
    """Rebuild the pool compactly in vertex order (CSR-like layout).

    * last-writer-wins on (owner, dst) by timestamp, tombstones dropped;
    * edges from/to deleted vertices dropped;
    * deleted vertex rows recycled into the free ring;
    * each live vertex gets ``cap = snapB + max(snapB, incomingB, 1)``
      blocks (2d discipline, pre-sized for ``incoming`` pending ops).

    Dispatch: the streaming block-row rebuild handles every state whose
    live extents fit the size segments (sizes <= dmax, segment counts
    within budget); anything else — and ``defrag_impl='dense'`` — runs
    the dense entry-scatter reference. Both produce identical states.
    """
    with jax.named_scope("defrag"):
        n_cap = vt.size.shape[0]
        if incoming is None:
            incoming = jnp.zeros((n_cap,), jnp.int32)
        if spec.defrag_impl == "dense":
            return _defrag_dense(spec, pool, vt, incoming)
        tiers, masks, stream_ok = defrag_segments(spec, vt)
        return jax.lax.cond(
            stream_ok,
            lambda args: _defrag_stream(spec, args[0], args[1], incoming,
                                        tiers, masks),
            lambda args: _defrag_dense(spec, args[0], args[1], incoming),
            (pool, vt))


# --------------------------------------------------------------------------
# batched edge updates (insert / update / delete): the paper's O(1) append
# --------------------------------------------------------------------------

def apply_edge_updates(spec: PoolSpec, pool: EdgePool, vt: VertexTable,
                       u: jnp.ndarray, v: jnp.ndarray, w: jnp.ndarray,
                       mask: jnp.ndarray):
    """Apply a batch of edge operations given vertex OFFSETS.

    ``w == 0`` is a deletion (paper: NULL weight log). Ops are timestamped
    ``clock + batch_index`` — the deterministic analogue of the paper's
    per-log fetch_add ordering. Returns (pool, vt, dropped) where ``dropped``
    is the number of masked ops that could not be applied (pool exhaustion);
    the distributed engine reports it per shard.

    The work runs in four named scopes, one level deep, which a profiler
    trace carries as each op's ``tf_op`` path: ``group`` (ops grouped by
    owner, tier masks), ``compact`` (the fast-path tiers or the ``defrag``
    rebuild), ``probe`` (pre-batch liveness of each distinct pair) and
    ``append`` (slot claims, the slot scatter, size and counter updates).
    With the Pallas append the window probe runs inside the ``append``
    kernel.
    """
    B = u.shape[0]
    bs = spec.block_size
    nb = pool.dst.shape[0]
    n_cap = vt.size.shape[0]
    dS = min(spec.probe_width, spec.dmax)
    two_tier = dS < spec.dmax
    KF = spec.k_big
    Ww = _fold_words(n_cap)

    with jax.named_scope("group"):
        valid = mask & (u >= 0) & (v >= 0)
        ts = pool.clock + jnp.arange(B, dtype=jnp.int32)

        g = _group_by(u, valid)
        guc = jnp.clip(g["gu"], 0, n_cap - 1)
        gsize = jnp.where(g["gvalid"], vt.size[guc], 0)
        gcap = jnp.where(g["gvalid"], vt.cap[guc], 0)
        need = gsize + g["gcount"]
        govf = g["gvalid"] & (need > gcap)

        # fast-path eligibility: whole current array fits the compaction
        # buffer (a vertex whose per-batch incoming exceeds dmax defrags
        # instead so the fast path's static extent bound always holds)
        small_ok = govf & (gcap <= spec.dmax) & (gsize <= spec.dmax) & \
            (g["gcount"] <= spec.dmax)
        n_ovf = jnp.sum(govf.astype(jnp.int32))
        n_small = jnp.sum(small_ok.astype(jnp.int32))
        jumbo = n_ovf != n_small

        # tiered fast path: first-touch vertices (no edge array at all — the
        # bulk of any ingest stream) take the whole-batch allocation tier;
        # in-window arrays compact at window width under the wide k_max
        # budget; the rare big vertex pays the full dmax-width gather under
        # the narrow k_big budget and hands the probe its liveness fold for
        # free.
        tier_a = small_ok & (gsize == 0) & (gcap == 0)
        rest = small_ok & ~tier_a
        tier_l = rest & (gsize > dS) if two_tier else jnp.zeros_like(rest)
        tier_s = rest & ~tier_l

        kuA = jnp.where(tier_a, g["gu"], -1)
        kincA = jnp.where(tier_a, g["gcount"], 0)
        base_log = spec.buf_blocks if spec.policy == "sorted" else 1
        worstA = jnp.sum(jnp.where(tier_a, jnp.maximum(_cdiv(kincA, bs),
                                                       base_log), 0))

        def _tier(mask, k_budget):
            kidx = jnp.nonzero(mask, size=k_budget, fill_value=B)[0]
            kmask = kidx < B
            kc = jnp.clip(kidx, 0, B - 1)
            ku = jnp.where(kmask, g["gu"][kc], -1)
            kinc = jnp.where(kmask, g["gcount"][kc], 0)
            truncated = jnp.sum(mask.astype(jnp.int32)) > k_budget
            # upper bound on blocks this tier may allocate:
            worst = jnp.sum(jnp.where(kmask,
                                      _cdiv(jnp.minimum(gsize[kc], spec.dmax),
                                            bs) * 2 + _cdiv(kinc, bs) + 2, 0))
            return ku, kmask, kinc, truncated, worst

        kuS, kmS, kincS, truncS, worstS = _tier(tier_s, spec.k_max)
        kuL, kmL, kincL, truncL, worstL = _tier(tier_l, spec.k_big)
        truncated = truncS | truncL
        pool_tight = pool.next_block + worstA + worstS + worstL > nb
        half_garbage = pool.garbage > (nb * bs) // 2
        do_defrag = jumbo | truncated | pool_tight | half_garbage

        incoming_vec = jnp.zeros((n_cap,), jnp.int32).at[
            jnp.where(g["gvalid"], g["gu"], n_cap)].add(g["gcount"],
                                                        mode="drop")

    def _defrag_path(args):
        pool, vt = args
        pool, vt = defrag(spec, pool, vt, incoming_vec)
        # defrag resynchronizes live_m exactly but rebuilds EVERY vertex, so
        # there is no per-vertex fold to hand the probe (over-window vertices
        # in a defrag batch flag dirty instead)
        return (pool, vt, jnp.full((KF,), -1, jnp.int32),
                jnp.zeros((KF, Ww), jnp.uint32))

    def _fast_path(args):
        pool, vt = args
        live = ~do_defrag
        pool, vt = _alloc_extents(spec, pool, vt, kuA, tier_a & live, kincA)
        pool, vt, _, _ = _compact_vertices(spec, pool, vt, kuS, kmS & live,
                                           kincS, dS, fold=False)
        if not two_tier:
            return (pool, vt, jnp.full((KF,), -1, jnp.int32),
                    jnp.zeros((KF, Ww), jnp.uint32))
        pool, vt, fku, fbm = _compact_vertices(spec, pool, vt, kuL,
                                               kmL & live, kincL, spec.dmax,
                                               fold=True)
        return pool, vt, fku, fbm

    with jax.named_scope("compact"):
        pool, vt, fold_ku, fold_bitmap = jax.lax.cond(
            do_defrag, _defrag_path, _fast_path, (pool, vt))

    # ---- append every op at size + rank (log append, O(1) per op) ----
    with jax.named_scope("append"):
        order = g["order"]
        su = g["su"]
        suc = jnp.clip(su, 0, n_cap - 1)
        base = jnp.where(su < INT_MAX, vt.size[suc], 0)
        slot = base + g["rank"]
        cap_now = jnp.where(su < INT_MAX, vt.cap[suc], 0)
        start = vt.start_block[suc]
        op_ok = (su < INT_MAX) & (slot < cap_now) & (start >= 0)
        dropped = jnp.sum(((su < INT_MAX) & ~op_ok).astype(jnp.int32))
        sv = v[order]
        sw_ = w[order]
        sts = ts[order]
        tgt_blk = jnp.where(op_ok, start + slot // bs, nb)

    # ---- incremental live-edge accounting (probed BEFORE the appends land):
    # a distinct (u, v) pair's post-batch liveness is decided by its LAST op;
    # its pre-batch liveness is probed against u's current entries (last-
    # writer-wins by timestamp — the same rule the snapshot applies), so
    #   delta = Σ_pairs applied(last op) · [(w_last != 0) − was_live]
    # keeps ``live_m`` exact without ever rebuilding a CSR. Probe sources, in
    # order of preference:
    #   1. the compaction FOLD — vertices compacted this batch already paid a
    #      (K, dmax) gather, whose deduped live set is returned as a bitmap,
    #      so their pairs are exact at any degree;
    #   2. a bounded-width window (``probe_width`` ≪ dmax) over the owner's
    #      entries — exact while the array fits the window; an over-window
    #      un-folded vertex could hide the pair's newest entry, so it flags
    #      the counter dirty instead of silently drifting.
    # Drops also make the counter unreliable (an earlier op of the pair may
    # have landed): dirty, resynchronized by the next defrag / host recount.
    pallas = kops.resolve("append", spec.append_impl) == "pallas"
    with jax.named_scope("probe"):
        op_ok_orig = jnp.zeros((B,), bool).at[order].set(op_ok)
        pu = jnp.where(valid, u, INT_MAX)
        pv = jnp.where(valid, v, INT_MAX)
        porder = jnp.lexsort((ts, pv, pu))   # (u, v, ts): last-per-pair = max ts
        u2, v2, w2 = pu[porder], pv[porder], w[porder]
        ok2 = op_ok_orig[porder]
        nu = jnp.concatenate([u2[1:], jnp.full((1,), -2, u2.dtype)])
        nv = jnp.concatenate([v2[1:], jnp.full((1,), -2, v2.dtype)])
        pair_last = ((u2 != nu) | (v2 != nv)) & (u2 < INT_MAX)

        u2c = jnp.clip(u2, 0, n_cap - 1)
        v2c = jnp.clip(v2, 0, n_cap - 1)
        k_of = jnp.full((n_cap + 1,), -1, jnp.int32).at[
            jnp.where(fold_ku >= 0, fold_ku, n_cap)].set(
                jnp.arange(KF, dtype=jnp.int32), mode="drop")[:n_cap]
        krow = jnp.where(pair_last, k_of[u2c], -1)
        fold_hit = krow >= 0
        fw = fold_bitmap[jnp.clip(krow, 0, KF - 1), v2c >> 5]
        fold_live = ((fw >> (v2c & 31).astype(jnp.uint32)) & 1) == 1

        probe_u = jnp.where(pair_last & ~fold_hit, u2, -1)
        p_start = jnp.where(probe_u >= 0, vt.start_block[u2c], -1)
        p_sz = jnp.where(probe_u >= 0, vt.size[u2c], 0)
        p_v = jnp.where(probe_u >= 0, v2, -1)

        if not pallas:
            Wp = min(spec.probe_width, spec.dmax)
            d_e, w_e, t_e, _ = _gather_vertex_entries(spec, pool, vt,
                                                      probe_u, Wp)
            t_match = jnp.where(d_e == v2[:, None], t_e, 0)  # clock starts at 1
            newest = jnp.argmax(t_match, axis=1)
            win_was_live = (jnp.max(t_match, axis=1) > 0) & \
                (w_e[jnp.arange(B), newest] != 0)
            probe_blind = jnp.any((probe_u >= 0) & (p_sz > Wp))

    with jax.named_scope("append"):
        # ---- touched-tile bound: the pool tiles any probe extent or landed
        # slot of this batch can live in. The Pallas append only VISITS these
        # (prefetched tile list; the grid's tail revisits the last touched
        # tile as a no-op), and ``tiles_scanned`` records the bound on both
        # paths — probe extents are marked as [first, last] tile RANGES via a
        # diff/cumsum cover, so even a post-jumbo extent wider than dmax stays
        # fully covered.
        T = kops.append_tile_rows(nb)
        n_tiles = nb // T
        p_rows = _cdiv(p_sz, bs)
        has_p = (p_start >= 0) & (p_rows > 0)
        t_first = jnp.where(has_p, p_start // T, n_tiles)
        t_end = jnp.where(has_p, (p_start + p_rows - 1) // T + 1, n_tiles)
        diff = jnp.zeros((n_tiles + 1,), jnp.int32).at[t_first].add(
            1, mode="drop").at[t_end].add(-1, mode="drop")
        touched = jnp.cumsum(diff[:n_tiles]) > 0
        wmark = jnp.zeros((n_tiles + 1,), bool).at[
            jnp.where(op_ok, tgt_blk // T, n_tiles)].set(True, mode="drop")
        touched = touched | wmark[:n_tiles]
        n_touched = jnp.sum(touched.astype(jnp.int32))
        t_order = jnp.nonzero(touched, size=n_tiles,
                              fill_value=0)[0].astype(jnp.int32)
        t_pad = t_order[jnp.clip(n_touched - 1, 0, n_tiles - 1)]
        tiles_list = jnp.where(jnp.arange(n_tiles, dtype=jnp.int32) <
                               n_touched, t_order, t_pad)

        if pallas:
            # fused append: slot scatter + full-extent last-writer probe in
            # one VMEM-resident pass per TOUCHED pool tile — exact liveness,
            # never blind, and never a full-pool scan
            nd, nw, nt, win_was_live = kops.append_edges(
                pool.dst, pool.weight, pool.ts, tgt_blk, slot % bs, op_ok,
                sv, sw_, sts, p_start, p_sz, p_v, tiles=tiles_list,
                n_touched=n_touched, impl="pallas")
            pool = pool._replace(dst=nd, weight=nw, ts=nt)
            probe_blind = jnp.zeros((), bool)
        else:
            pool = _scatter_entries(pool, tgt_blk, slot % bs, op_ok, sv, sw_,
                                    sts)

        was_live = jnp.where(fold_hit, fold_live, win_was_live)
        delta = jnp.sum(jnp.where(pair_last & ok2,
                                  (w2 != 0).astype(jnp.int32) -
                                  was_live.astype(jnp.int32), 0))

        # size += written count per group
        wrote = op_ok.astype(jnp.int32)
        wrote_per_group = jnp.zeros((B,), jnp.int32).at[g["gid"]].add(
            jnp.where(su < INT_MAX, wrote, 0))
        gtgt = jnp.where(g["gvalid"], g["gu"], n_cap)
        vt = vt._replace(size=vt.size.at[gtgt].add(
            wrote_per_group, mode="drop"))

        # updates/deletes eventually strand one stale entry each; a cheap
        # upper estimate (¼ of writes) drives the proactive half-garbage
        # defrag trigger
        pool = pool._replace(
            clock=pool.clock + B,
            garbage=pool.garbage + jnp.sum(wrote) // 4,
            overflow=pool.overflow + jnp.where(dropped > 0, 1, 0),
            live_m=pool.live_m + delta,
            live_dirty=jnp.maximum(
                pool.live_dirty,
                ((dropped > 0) | probe_blind).astype(jnp.int32)),
            tiles_scanned=pool.tiles_scanned + n_touched)
    return pool, vt, dropped


# --------------------------------------------------------------------------
# reads
# --------------------------------------------------------------------------

def get_neighbors(spec: PoolSpec, pool: EdgePool, vt: VertexTable,
                  u: jnp.ndarray, read_ts=None, width: int | None = None):
    """MVCC get-neighbors for a batch of vertex offsets.

    Returns (dst, weight, ts, count) with rows front-packed in reverse-scan
    order (paper's get_ngbrs = compaction-style scan, O(d)). Named scopes:
    ``gather`` (the (keys x width) entry gather and the visibility filter),
    ``compact`` (``compact_rows``)."""
    width = spec.dmax if width is None else width
    n_cap = vt.size.shape[0]
    with jax.named_scope("gather"):
        d, w, t, size = _gather_vertex_entries(spec, pool, vt, u, width)
        # destination-visibility filter (paper: Del_time makes a vertex
        # invisible)
        dt = vt.del_time[jnp.clip(d, 0, n_cap - 1)]
        if read_ts is None:
            dead = (d >= 0) & (dt != 0)
        else:
            rts = jnp.asarray(read_ts, jnp.int32)
            dead = (d >= 0) & (((dt > 0) & (dt <= rts)) | (dt == -1))
        d = jnp.where(dead, -1, d)
    with jax.named_scope("compact"):
        rts = None if read_ts is None else jnp.asarray(read_ts, jnp.int32)
        return kops.compact_rows(d, w, t, size, read_ts=rts,
                                 impl=spec.compact_impl)


def live_edges(spec: PoolSpec, pool: EdgePool, vt: VertexTable, read_ts=None):
    """Flat snapshot of live edges: (owner, dst, weight, ts, keep_mask),
    sorted by (owner, dst). Input to analytics CSR construction."""
    bs = spec.block_size
    nb = pool.dst.shape[0]
    n_cap = vt.size.shape[0]
    N = nb * bs
    own = jnp.repeat(pool.owner, bs)
    d = pool.dst.reshape(-1)
    w = pool.weight.reshape(-1)
    t = pool.ts.reshape(-1)
    blk_index = jnp.arange(N, dtype=jnp.int32) // bs
    lane = jnp.arange(N, dtype=jnp.int32) % bs
    ownc = jnp.clip(own, 0, n_cap - 1)
    start = vt.start_block[ownc]
    pos = (blk_index - start) * bs + lane
    occupied = (own >= 0) & (pos >= 0) & (pos < vt.size[ownc])
    alive = (vt.del_time[ownc] == 0)
    dstc = jnp.clip(d, 0, n_cap - 1)
    dst_ok = (d >= 0) & (vt.del_time[dstc] == 0)
    valid = occupied & alive & dst_ok
    if read_ts is not None:
        valid = valid & (t <= jnp.asarray(read_ts, jnp.int32))
    SENT = INT_MAX
    so = jnp.where(valid, own, SENT)
    sd = jnp.where(valid, d, SENT)
    stv = jnp.where(valid, t, 0)
    order = jnp.lexsort((stv, sd, so))
    so, sd, sw, stv = so[order], sd[order], w[order], stv[order]
    nxt_o = jnp.concatenate([so[1:], jnp.full((1,), -2, so.dtype)])
    nxt_d = jnp.concatenate([sd[1:], jnp.full((1,), -2, sd.dtype)])
    is_last = ((so != nxt_o) | (sd != nxt_d)) & (so < SENT)
    keep = is_last & (sw != 0)
    return so, sd, sw, stv, keep
