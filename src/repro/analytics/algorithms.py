"""GAPBS-style analytics over a RadixGraph snapshot (paper §4.4).

All algorithms run on the CSR ``GraphSnapshot`` whose ``dst`` column holds
vertex *offsets* — the paper's edge chain: after the initial source lookup,
no vertex-index access ever happens (Fig. 6). Everything is jit-compatible
with `lax.while_loop` level iteration and segment reductions (TPU-friendly:
the hot loop is gathers + scatter-reduce over the flat edge array).

The edge-chain ablation (paper Table 6) is benchmarked by routing each hop
through IDs + SORT lookups instead — see benchmarks/table6_ablation.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

INF = np.float32(3.4e38)


def edge_sources(indptr: jnp.ndarray, m_cap: int) -> jnp.ndarray:
    """src offset of every CSR edge slot (searchsorted over indptr)."""
    e = jnp.arange(m_cap, dtype=jnp.int32)
    return (jnp.searchsorted(indptr, e, side="right") - 1).astype(jnp.int32)


def _edge_valid(snap):
    m_cap = snap.dst.shape[0]
    e = jnp.arange(m_cap, dtype=jnp.int32)
    return e < snap.m


# --------------------------------------------------------------------------
# shard-local phases
#
# The per-level / per-iteration edge work of BFS and PageRank only ever
# touches the LOCAL CSR: these phases are shared verbatim by the single-shard
# algorithms below and by ``dist.graph_engine``, whose distributed loops run
# one local phase per shard and then exchange frontiers / inflows over the
# mesh axis (the combine phase).
# --------------------------------------------------------------------------

def csr_edges(snap):
    """Loop-invariant local edge view (src row, validity, routed dst) —
    build it ONCE outside a level/iteration loop and pass it to the phases
    below, so the O(m_cap) searchsorted is never recomputed per level."""
    n = snap.indptr.shape[0] - 1
    src = edge_sources(snap.indptr, snap.dst.shape[0])
    ok = _edge_valid(snap)
    dst = jnp.where(ok, snap.dst, n)  # out-of-range -> dropped
    return src, ok, dst


def bfs_expand(snap, frontier: jnp.ndarray, edges=None) -> jnp.ndarray:
    """One level expansion over the local CSR: bool[n] frontier -> bool[n]
    rows hit by an out-edge of a frontier row."""
    n = snap.indptr.shape[0] - 1
    src, ok, dst = edges if edges is not None else csr_edges(snap)
    live = ok & frontier[jnp.clip(src, 0, n - 1)]
    return jnp.zeros((n + 1,), bool).at[jnp.where(live, dst, n)].max(
        True)[:n]


def pagerank_contrib(snap, pr: jnp.ndarray) -> jnp.ndarray:
    """Per-row outgoing contribution pr/deg (0 for dangling rows)."""
    deg = (snap.indptr[1:] - snap.indptr[:-1]).astype(jnp.float32)
    return jnp.where(deg > 0, pr / jnp.maximum(deg, 1.0), 0.0)


def pagerank_scatter(snap, contrib: jnp.ndarray, edges=None) -> jnp.ndarray:
    """Scatter contributions along local CSR edges: float[n] -> inflow[n]."""
    n = snap.indptr.shape[0] - 1
    src, ok, dst = edges if edges is not None else csr_edges(snap)
    return jnp.zeros((n + 1,)).at[dst].add(
        jnp.where(ok, contrib[jnp.clip(src, 0, n - 1)], 0.0))[:n]


@functools.partial(jax.jit, static_argnames=("max_iters",))
def bfs(snap, source: jnp.ndarray, max_iters: int = 64):
    """Level-synchronous BFS. Returns int32 depth per offset (-1 unreachable)."""
    n = snap.indptr.shape[0] - 1
    edges = csr_edges(snap)

    depth0 = jnp.full((n,), -1, jnp.int32).at[source].set(0)
    frontier0 = jnp.zeros((n,), bool).at[source].set(True)

    def cond(c):
        depth, frontier, it = c
        return jnp.any(frontier) & (it < max_iters)

    def body(c):
        depth, frontier, it = c
        nxt = bfs_expand(snap, frontier, edges) & (depth < 0)
        depth = jnp.where(nxt, it + 1, depth)
        return depth, nxt, it + 1

    depth, _, _ = jax.lax.while_loop(cond, body, (depth0, frontier0,
                                                  jnp.int32(0)))
    return depth


@functools.partial(jax.jit, static_argnames=("max_iters",))
def sssp(snap, source: jnp.ndarray, max_iters: int = 64):
    """Bellman-Ford (non-negative weights). float32 distances, INF=unreached."""
    n = snap.indptr.shape[0] - 1
    m_cap = snap.dst.shape[0]
    src = edge_sources(snap.indptr, m_cap)
    ok = _edge_valid(snap)
    dst = jnp.where(ok, snap.dst, n)
    w = jnp.where(ok, snap.weight, 0.0)

    dist0 = jnp.full((n,), INF).at[source].set(0.0)

    def cond(c):
        dist, changed, it = c
        return changed & (it < max_iters)

    def body(c):
        dist, _, it = c
        cand = jnp.where(ok, dist[jnp.clip(src, 0, n - 1)] + w, INF)
        relax = jnp.full((n + 1,), INF).at[dst].min(cand)
        nd = jnp.minimum(dist, relax[:n])
        return nd, jnp.any(nd < dist), it + 1

    dist, _, _ = jax.lax.while_loop(cond, body, (dist0, jnp.bool_(True),
                                                 jnp.int32(0)))
    return dist


@functools.partial(jax.jit, static_argnames=("iters",))
def pagerank(snap, iters: int = 20, damping: float = 0.85):
    deg = (snap.indptr[1:] - snap.indptr[:-1]).astype(jnp.float32)
    edges = csr_edges(snap)
    active = snap.active
    n_act = jnp.maximum(jnp.sum(active.astype(jnp.float32)), 1.0)

    pr0 = jnp.where(active, 1.0 / n_act, 0.0)

    def step(pr, _):
        contrib = pagerank_contrib(snap, pr)
        dangling = jnp.sum(jnp.where(active & (deg == 0), pr, 0.0))
        inflow = pagerank_scatter(snap, contrib, edges)
        pr = jnp.where(active,
                       (1 - damping) / n_act + damping * (inflow + dangling / n_act),
                       0.0)
        return pr, None

    pr, _ = jax.lax.scan(step, pr0, None, length=iters)
    return pr


@functools.partial(jax.jit, static_argnames=("max_iters",))
def wcc(snap, max_iters: int = 64):
    """Weakly connected components by min-label propagation + pointer jumping.
    Assumes edges inserted symmetrically (paper treats graphs as undirected)."""
    n = snap.indptr.shape[0] - 1
    m_cap = snap.dst.shape[0]
    src = edge_sources(snap.indptr, m_cap)
    ok = _edge_valid(snap)
    dst = jnp.where(ok, snap.dst, n)
    label0 = jnp.where(snap.active, jnp.arange(n, dtype=jnp.int32), n)

    def cond(c):
        lab, changed, it = c
        return changed & (it < max_iters)

    def body(c):
        lab, _, it = c
        cand = jnp.where(ok, lab[jnp.clip(src, 0, n - 1)], n)
        pull = jnp.full((n + 1,), n, jnp.int32).at[dst].min(cand)
        nl = jnp.minimum(lab, pull[:n])
        # pointer jumping (hook): label <- label[label]
        nl = jnp.minimum(nl, nl[jnp.clip(nl, 0, n - 1)])
        return nl, jnp.any(nl < lab), it + 1

    lab, _, _ = jax.lax.while_loop(cond, body, (label0, jnp.bool_(True),
                                                jnp.int32(0)))
    return jnp.where(snap.active, lab, -1)


@jax.jit
def triangle_count(snap):
    """Triangle count via sorted-adjacency merge on the CSR (undirected,
    symmetric edges; each triangle counted 6x as directed wedges).

    Vectorized merge: for each edge (u, v) count |N(u) ∩ N(v)| using
    searchsorted over v's sorted adjacency — O(m·lg d) gathers, segment-sum.
    Suitable for the benchmark scale; the dominant cost is intersection, as
    the paper notes (§4.4 TC gains are limited for RadixGraph).
    """
    n = snap.indptr.shape[0] - 1
    m_cap = snap.dst.shape[0]
    src = edge_sources(snap.indptr, m_cap)
    ok = _edge_valid(snap)
    dst = jnp.where(ok, snap.dst, 0)
    srcc = jnp.clip(src, 0, n - 1)

    # For every edge e=(u,v) and every neighbor w of u (same CSR row as e),
    # test membership w in N(v) by binary search. We bound row width by
    # iterating over "wedge slots": edge e x row position handled by a
    # flat loop over m_cap via membership of each edge's dst in N(src-dst).
    # Count wedges (u->v, v->w) where w in N(u):
    # for each edge f=(v,w): for u it belongs as second hop of edges into v.
    # Simpler equivalent: sum over edges f=(v,w) of |N(v) ∩ N(w)| gives
    # 2x directed triangle closures; with full symmetry total/6.
    lo = snap.indptr[jnp.clip(dst, 0, n - 1)]
    hi = snap.indptr[jnp.clip(dst, 0, n - 1) + 1]

    # Wedge formulation: for edge e=(u,v) and each neighbor w = N(u)[r],
    # triangle iff (v,w) is an edge — tested by binary search over v's sorted
    # CSR row [lo, hi). Each triangle is counted 6x (3 pivots x 2 orders).
    # Static shapes require capping the per-row scan at DMAX_TRI.
    DMAX_TRI = 256
    row_start = snap.indptr[srcc]
    deg_u = snap.indptr[srcc + 1] - row_start

    def body(r, acc):
        e2 = row_start + r
        in_row = (r < deg_u) & ok
        w = jnp.where(in_row, snap.dst[jnp.clip(e2, 0, m_cap - 1)], -1)
        # per-edge binary search for w over the row [lo, hi) of v:
        l, h = lo, hi

        def bs(_, lh):
            l, h = lh
            mid = (l + h) // 2
            val = snap.dst[jnp.clip(mid, 0, m_cap - 1)]
            go_r = val < w
            return jnp.where(go_r, mid + 1, l), jnp.where(go_r, h, mid)

        l, h = jax.lax.fori_loop(0, 32, bs, (l, h))
        found = (l < hi) & (snap.dst[jnp.clip(l, 0, m_cap - 1)] == w) & (w >= 0)
        return acc + jnp.sum((found & in_row).astype(jnp.int32))

    total = jax.lax.fori_loop(0, DMAX_TRI, body, jnp.int32(0))
    return total // 6


@functools.partial(jax.jit, static_argnames=("max_depth",))
def bc(snap, sources: jnp.ndarray, max_depth: int = 32):
    """Brandes betweenness (unweighted, sampled sources), GAPBS-style.

    Forward: level-synchronous BFS accumulating path counts sigma; backward:
    dependency accumulation over levels. Returns centrality per offset.
    """
    n = snap.indptr.shape[0] - 1
    m_cap = snap.dst.shape[0]
    src = edge_sources(snap.indptr, m_cap)
    ok = _edge_valid(snap)
    dst = jnp.where(ok, snap.dst, n)
    srcc = jnp.clip(src, 0, n - 1)

    def one_source(s):
        depth = jnp.full((n,), -1, jnp.int32).at[s].set(0)
        sigma = jnp.zeros((n,), jnp.float32).at[s].set(1.0)

        def fwd2(i, c):
            depth, sigma = c
            on_lvl = depth[srcc] == i
            add = jnp.zeros((n + 1,)).at[dst].add(
                jnp.where(ok & on_lvl, sigma[srcc], 0.0))[:n]
            newly = (add > 0) & (depth < 0)
            depth = jnp.where(newly, i + 1, depth)
            sigma = jnp.where(depth == i + 1, sigma + add, sigma)
            return depth, sigma

        depth, sigma = jax.lax.fori_loop(0, max_depth, fwd2, (depth, sigma))

        delta = jnp.zeros((n,), jnp.float32)

        def bwd(k, delta):
            lvl = max_depth - 1 - k
            # edges u->v with depth[u]==lvl, depth[v]==lvl+1
            du = depth[srcc]
            dv = depth[jnp.clip(dst, 0, n - 1)]
            onedge = ok & (du == lvl) & (dv == lvl + 1)
            contrib = jnp.where(onedge,
                                (sigma[srcc] / jnp.maximum(
                                    sigma[jnp.clip(dst, 0, n - 1)], 1.0)) *
                                (1.0 + delta[jnp.clip(dst, 0, n - 1)]), 0.0)
            acc = jnp.zeros((n + 1,)).at[jnp.where(onedge, srcc, n)].add(
                contrib)[:n]
            return delta + acc

        delta = jax.lax.fori_loop(0, max_depth, bwd, delta)
        return delta.at[s].set(0.0)

    deltas = jax.vmap(one_source)(sources)
    return jnp.sum(deltas, axis=0)


@functools.partial(jax.jit, static_argnames=("k",))
def khop(snap, sources: jnp.ndarray, k: int = 2):
    """k-hop neighborhood sizes for a batch of source offsets (paper §4.4).
    Only the initial sources required a SORT lookup — the hops run entirely
    on offsets (edge chain)."""
    n = snap.indptr.shape[0] - 1
    m_cap = snap.dst.shape[0]
    src = edge_sources(snap.indptr, m_cap)
    ok = _edge_valid(snap)
    dst = jnp.where(ok, snap.dst, n)
    srcc = jnp.clip(src, 0, n - 1)

    def one(s):
        seen = jnp.zeros((n,), bool).at[s].set(True)
        frontier = seen

        def hop(_, c):
            seen, frontier = c
            live = ok & frontier[srcc]
            hit = jnp.zeros((n + 1,), bool).at[jnp.where(live, dst, n)].max(
                True)[:n]
            nf = hit & ~seen
            return seen | nf, nf

        seen, _ = jax.lax.fori_loop(0, k, hop, (seen, frontier))
        return jnp.sum(seen.astype(jnp.int32)) - 1

    return jax.vmap(one)(sources)
