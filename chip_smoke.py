#!/usr/bin/env python3
"""Chip smoke: drive the GraphStore ingest/read/analytics path once on a TPU
and check every answer against a plain numpy reference.

    python3 chip_smoke.py              # one chip: LocalStore
    python3 chip_smoke.py --chips 4    # four chips: ShardedStore, n_shards=4

The graph is the uniform random graph of the RadixGraph paper's "u" sets at
Graph500's edgefactor: 2^n vertices with random, non-contiguous 32-bit IDs
and 16 * 2^n undirected edge inserts through ``store.apply``, then a tail
of weight updates and about 5% tombstone deletes. The target is n = 20;
the default is cut from it by powers of two, each cut printed with its
reason (``CUTS``). Capacities are sized from the edge count. The checks:
``num_edges``, ``degree`` and ``neighbors`` (IDs and weights) for 4096
sampled vertices and BFS depths from one source must equal the reference
exactly; PageRank (10 iterations, float32 on the device) must agree with
the float64 reference within ``PR_RTOL``.

Run from the repository root. The script exits non-zero, printing no result
line, when JAX finds no TPU or any check fails. Earlier lines report host
wall seconds per phase (not device metrics), the device's peak memory, the
implementation each main-path kernel resolved to, and whether the lowered
apply program holds a Pallas kernel (``tpu_custom_call``). The last line is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import collections
import json
import pathlib
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

PR_ITERS = 10
PR_RTOL = 1e-4         # float32 device sums vs the float64 reference
N_SAMPLE = 4096
BLOCK = 16

# The target scale is 2^20 vertices; each power-of-two cut from it, and why.
# The edge-update step costs a roughly fixed time per 4096-entry batch (its
# gathers and scatters have static sizes): 2^20 vertices take ~8,900
# batches, 2^19 ~4,450. The run prints its steady ingest rate and what the
# 2^20 stream would take at it.
TARGET_LOG2_V = 20
CUTS = {19: "a 2^19 run on one TPU v5e ingested 48.8 s per 2^20 inserts "
            "after compilation (~95 ms per 4096-entry batch), so the 2^20 "
            "insert stream alone takes ~781 s; with the step's ~140 s "
            "compile, the tail, the rebuild and the analytics the command "
            "passes the 1200 s run limit"}
DEFAULT_LOG2_V = min(CUTS, default=TARGET_LOG2_V)


T0 = time.perf_counter()


def log(*a):
    print(f"[{time.perf_counter() - T0:8.1f}s]", *a, flush=True)


# --------------------------------------------------------------------------
# the stream and its numpy reference
# --------------------------------------------------------------------------

def make_stream(log2_v: int, edgefactor: int, seed: int):
    """Vertex IDs, the insert stream and the update/delete tail (numpy)."""
    rng = np.random.default_rng(seed)
    V = 1 << log2_v
    E = edgefactor * V
    ids = rng.choice(1 << 32, V, replace=False).astype(np.uint64)
    su = rng.integers(0, V, E)
    sv = rng.integers(0, V, E)
    w = rng.uniform(0.5, 2.0, E).astype(np.float32)
    n_upd, n_del = E // 32, E // 20
    pick = rng.choice(E, n_upd + n_del, replace=False)
    tw = np.concatenate([rng.uniform(2.0, 4.0, n_upd).astype(np.float32),
                         np.zeros(n_del, np.float32)])
    perm = rng.permutation(len(pick))
    return dict(V=V, E=E, ids=ids, su=su, sv=sv, w=w,
                tu=su[pick][perm], tv=sv[pick][perm], tw=tw[perm])


class Reference:
    """Last-writer-wins directed edge set of the undirected op stream (op
    i lands as (u, v) then (v, u)), as a CSR over vertex indices."""

    def __init__(self, s):
        V = s["V"]
        u = np.concatenate([s["su"], s["tu"]])
        v = np.concatenate([s["sv"], s["tv"]])
        w = np.concatenate([s["w"], s["tw"]])
        key = np.empty(2 * len(u), np.int64)
        key[0::2] = u * V + v
        key[1::2] = v * V + u
        ww = np.repeat(w, 2)
        # last occurrence of each directed pair wins
        rk = key[::-1]
        uk, first = np.unique(rk, return_index=True)
        last_w = ww[::-1][first]
        live = last_w != 0
        self.key, self.w = uk[live], last_w[live]        # sorted by (u, v)
        self.src = (self.key // V).astype(np.int64)
        self.dst = (self.key % V).astype(np.int64)
        self.indptr = np.zeros(V + 1, np.int64)
        np.cumsum(np.bincount(self.src, minlength=V), out=self.indptr[1:])
        self.V = V
        # every endpoint ever named is a registered (active) vertex
        self.present = np.zeros(V, bool)
        self.present[u] = True
        self.present[v] = True

    @property
    def num_edges(self) -> int:
        return len(self.key)

    def degree(self, x):
        return self.indptr[x + 1] - self.indptr[x]

    def bfs(self, source: int) -> np.ndarray:
        depth = np.full(self.V, -1, np.int64)
        depth[source] = 0
        frontier = np.array([source])
        level = 0
        while frontier.size:
            level += 1
            on = np.zeros(self.V, bool)
            on[frontier] = True
            nb = np.unique(self.dst[on[self.src]])
            nb = nb[depth[nb] < 0]
            depth[nb] = level
            frontier = nb
        return depth

    def pagerank(self, iters: int, damping: float = 0.85) -> np.ndarray:
        n = float(self.present.sum())
        deg = np.diff(self.indptr).astype(np.float64)
        pr = np.where(self.present, 1.0 / n, 0.0)
        for _ in range(iters):
            contrib = np.where(deg > 0, pr / np.maximum(deg, 1.0), 0.0)
            dangling = pr[self.present & (deg == 0)].sum()
            inflow = np.bincount(self.dst, weights=contrib[self.src],
                                 minlength=self.V)
            pr = np.where(self.present,
                          (1 - damping) / n + damping * (inflow + dangling / n),
                          0.0)
        return pr


# --------------------------------------------------------------------------
# the stores
# --------------------------------------------------------------------------

def _pow2_at_least(x: int) -> int:
    return 1 << max(0, int(x - 1).bit_length())


def store_kwargs(V: int, E: int, n_shards: int) -> dict:
    """Capacities from the edge count: every live directed entry needs a
    pool slot, the snaplog extents keep up to 2x that after a rebuild, and
    growth between rebuilds takes the rest of a 4x pool."""
    entries = 2 * E // n_shards                  # directed entries per shard
    pool_blocks = _pow2_at_least(4 * entries // BLOCK)
    common = dict(key_bits=32, block_size=BLOCK, pool_blocks=pool_blocks,
                  k_max=1024, dmax=4096, undirected=True)
    if n_shards == 1:
        return dict(common, n_max=V + V // 4, expected_n=V, batch=4096,
                    m_cap=2 * E)
    # a shard's vertex table holds its own sources AND every destination
    # its edges name: on a uniform graph, nearly every vertex
    return dict(common, n_shards=n_shards, n_per_shard=V + V // 4,
                expected_n=V, batch=4096 * n_shards,
                query_batch=256 * n_shards,
                m_cap=_pow2_at_least(entries + entries // 4))


def compile_aside(store, kw: dict, probe_id: int) -> list:
    """Start the sharded store's read, analytics and vertex-sync programs
    compiling on side threads while the ingest compiles its own. Each runs
    once on an empty store of the same spec (the sync program is built as
    the store builds it), so the persistent compile cache serves it to the
    ingested store. For a v5e mesh these are five compiles of ~1-2 min
    each that would otherwise run one after another. Returns the futures."""
    from concurrent.futures import ThreadPoolExecutor
    import jax
    import jax.numpy as jnp
    from repro.api import AnalyticsOp, ReadOp, make_store
    from repro.dist import graph_engine as ge
    empty = make_store("sharded", devices=list(store.mesh.devices.flat),
                       **kw)
    state = empty.state              # built once, before the threads share it
    sync = jax.jit(ge.make_sync_vertices(
        store.sspec, store.pspec, store.mesh, store.axis,
        budget=store.sync_budget, incremental=True))
    jobs = (lambda: empty.read(ReadOp("num_edges")),
            lambda: empty.read(ReadOp("degree", ids=[probe_id])),
            lambda: empty.analytics(AnalyticsOp(
                "bfs", {"source": probe_id, "max_iters": 32})),
            lambda: empty.analytics(AnalyticsOp(
                "pagerank", {"iters": PR_ITERS})),
            lambda: sync(state, jnp.zeros((store.n_shards,), jnp.int32)))
    pool = ThreadPoolExecutor(len(jobs))
    futures = [pool.submit(j) for j in jobs]
    pool.shutdown(wait=False)
    return futures


def rebuild(store) -> None:
    """Force one rebuild of a LocalStore's pool through its graph's
    explicit defrag, after checking that it takes the streaming path (the
    one ``defrag_rows`` runs in) rather than the dense fallback."""
    from repro.core import edgepool as ep
    g = store.graph
    _, _, streams = ep.defrag_segments(g.pool_spec, g.state.vt)
    assert bool(streams), "the forced rebuild would take the dense path"
    d0 = g.num_defrags
    g.defrag()
    assert g.num_defrags == d0 + 1, "the forced rebuild did not run"


def apply_program_text(store) -> str:
    """StableHLO of the store's per-batch apply program, lowered for the
    store's own state and batch shapes."""
    import jax
    import jax.numpy as jnp
    from repro.core import radixgraph as rg
    if store.backend == "local":
        g = store.graph
        B = g.batch
        keys = jnp.zeros((B, 2), jnp.uint32)
        return rg._update_edges_donate.lower(
            g.sort_spec, g.pool_spec, g.state, keys, keys,
            jnp.zeros((B,), jnp.float32), jnp.zeros((B,), bool)).as_text()
    B = store.batch
    keys = jnp.zeros((B, 2), jnp.uint32)
    return store.apply_program().lower(
        store.state, keys, keys, jnp.zeros((B,), jnp.float32),
        jnp.zeros((B,), bool)).as_text()


def run(log2_v: int = 20, edgefactor: int = 16, n_shards: int = 1,
        seed: int = 0, chunk: int = 1 << 20) -> dict:
    """Build, ingest, read and check. Returns the phase record; raises
    AssertionError on the first mismatch."""
    import jax
    from repro.api import AnalyticsOp, OpBatch, ReadOp, make_store
    from repro.kernels import ops as kops

    t = {}
    t0 = time.perf_counter()
    s = make_stream(log2_v, edgefactor, seed)
    ref = Reference(s)
    t["reference_s"] = time.perf_counter() - t0
    V, E, ids = s["V"], s["E"], s["ids"]
    kw = store_kwargs(V, E, n_shards)
    log(f"graph: {V} vertices, {E} undirected edge inserts, "
        f"{len(s['tu'])} tail ops; store {kw}")

    t0 = time.perf_counter()
    t["rebuild_s"] = 0.0
    aside = []
    if n_shards == 1:
        store = make_store("local", **kw)
        pspec = store.graph.pool_spec
    else:
        store = make_store("sharded", devices=jax.devices()[:n_shards], **kw)
        pspec = store.pspec
        aside = compile_aside(store, kw, int(ids[0]))
    # one chip: rebuild the pool once, early, while nearly every extent is
    # one block and the rebuild streams; the later checks cover its output
    rebuild_at = E // 8
    bounds = sorted(set(range(0, E, chunk)) | {rebuild_at, E})
    marks = []                         # (inserts applied, host clock)
    for lo, hi in zip(bounds, bounds[1:]):
        r = store.apply(OpBatch.edges(ids[s["su"][lo:hi]],
                                      ids[s["sv"][lo:hi]], s["w"][lo:hi]))
        assert r.dropped == 0, f"inserts dropped at op {lo}: {r}"
        log(f"ingest: {hi} of {E} inserts applied")
        if n_shards == 1 and hi == rebuild_at:
            t1 = time.perf_counter()
            rebuild(store)
            t["rebuild_s"] = time.perf_counter() - t1
            log(f"rebuild: streaming rebuild after {hi} inserts")
        marks.append((hi, time.perf_counter()))
    r = store.apply(OpBatch.edges(ids[s["tu"]], ids[s["tv"]], s["tw"]))
    assert r.dropped == 0, f"tail ops dropped: {r}"
    jax.block_until_ready(store.capture().state)
    t["ingest_s"] = time.perf_counter() - t0 - t["rebuild_s"]
    if len(marks) >= 3:    # the first chunk holds the step's compilation
        (n0, c0), (n1, c1) = marks[1], marks[-1]
        t["steady_ingest_s_per_insert"] = (c1 - c0) / (n1 - n0)
    dispatches = store.stats["super_batches"]
    log(f"ingest: {E + len(s['tu'])} ops in {dispatches} dispatches, "
        f"defrags={store.stats['defrags']}")
    if aside:
        t0 = time.perf_counter()
        for f in aside:
            f.result()
        t["side_compile_wait_s"] = time.perf_counter() - t0
        log(f"side compiles: {len(aside)} programs ready")

    # persistent compile cache hits and misses from here on: they show
    # whether the side compiles reached the ingested store
    cache = collections.Counter()

    def count_cache(event, **_):
        if event.startswith("/jax/compilation_cache/cache_"):
            cache[event.rsplit("_", 1)[-1]] += 1

    jax.monitoring.register_event_listener(count_cache)
    rng = np.random.default_rng(seed + 1)
    sample = rng.choice(V, min(N_SAMPLE, V), replace=False)
    t0 = time.perf_counter()
    m = store.read(ReadOp("num_edges"))
    assert m == ref.num_edges, f"num_edges {m} != reference {ref.num_edges}"
    deg = np.asarray(store.read(ReadOp("degree", ids=ids[sample])))
    assert np.array_equal(deg, ref.degree(sample)), "degree mismatch"
    nbrs = store.read(ReadOp("neighbors", ids=ids[sample]))
    for x, (nid, nw) in zip(sample, nbrs):
        lo, hi = ref.indptr[x], ref.indptr[x + 1]
        want = sorted(zip(ids[ref.dst[lo:hi]].tolist(), ref.w[lo:hi].tolist()))
        got = sorted(zip(np.asarray(nid).tolist(), np.asarray(nw).tolist()))
        assert got == want, f"neighbors of {ids[x]} differ"
    t["reads_s"] = time.perf_counter() - t0
    log(f"reads: num_edges={m}, degree and neighbors of {len(sample)} "
        f"vertices match")

    source = int(sample[np.argmax(ref.degree(sample))])
    t0 = time.perf_counter()
    got = store.analytics(AnalyticsOp("bfs", {"source": int(ids[source]),
                                              "max_iters": 32}))
    t["bfs_s"] = time.perf_counter() - t0
    want = ref.bfs(source)
    live = np.nonzero(ref.present)[0]
    assert set(got) == set(ids[live].tolist()), "BFS vertex set differs"
    assert all(got[int(ids[x])] == want[x] for x in live), "BFS depths differ"
    log(f"bfs: {int((want >= 0).sum())} reached, max depth {want.max()}")

    t0 = time.perf_counter()
    got = store.analytics(AnalyticsOp("pagerank", {"iters": PR_ITERS}))
    t["pagerank_s"] = time.perf_counter() - t0
    want = ref.pagerank(PR_ITERS)
    pr = np.array([got[int(ids[x])] for x in live])
    err = float(np.max(np.abs(pr - want[live]) / want[live]))
    assert err <= PR_RTOL, f"PageRank max relative error {err} > {PR_RTOL}"
    log(f"pagerank: max relative error {err:.3e} (limit {PR_RTOL})")
    jax.monitoring.unregister_event_listener(count_cache)
    log(f"compile cache during reads and analytics: {cache['hits']} hits, "
        f"{cache['misses']} misses")

    if n_shards > 1:
        devs = {d for d in jax.devices()[:n_shards]}
        for leaf in jax.tree.leaves(store.state):
            on = {sh.device for sh in leaf.addressable_shards}
            assert on == devs, f"state leaf {leaf.shape} on {on}"
        log(f"sharding: every state leaf has shards on all {n_shards} "
            f"devices")

    impls = {k: kops.resolve(k, knob) for k, knob in (
        ("append", pspec.append_impl), ("compact_rows", pspec.compact_impl),
        ("defrag_rows", pspec.compact_impl))}
    text = apply_program_text(store)
    rec = dict(phase_host_wall_s=t, impl=impls, ingest_dispatches=dispatches,
               defrags=store.stats["defrags"], compile_cache=dict(cache),
               apply_program_has_tpu_custom_call="tpu_custom_call" in text)
    stats = jax.devices()[0].memory_stats() or {}
    rec["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    return rec


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: LocalStore on one chip; 4: only the sharded "
                         "store over four chips")
    ap.add_argument("--log2-vertices", type=int, default=DEFAULT_LOG2_V)
    ap.add_argument("--edgefactor", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.runtime import init_compile_cache
    init_compile_cache()
    import jax
    dev = jax.devices()
    if dev[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {dev[0].platform!r})",
              file=sys.stderr)
        return 2
    if len(dev) < args.chips:
        print(f"chip_smoke: {args.chips} chips asked, {len(dev)} found",
              file=sys.stderr)
        return 2
    for lv in range(TARGET_LOG2_V - 1, args.log2_vertices - 1, -1):
        log(f"scale cut: 2^{lv + 1} -> 2^{lv} vertices: "
            f"{CUTS.get(lv, 'asked for on the command line')}")
    rec = run(args.log2_vertices, args.edgefactor, args.chips, args.seed)
    for k, v in rec["phase_host_wall_s"].items():
        log(f"host wall seconds {k}: {v!r}")
    log(f"host wall seconds per ingest dispatch, compilation included: "
        f"{rec['phase_host_wall_s']['ingest_s'] / rec['ingest_dispatches']!r}")
    per = rec["phase_host_wall_s"].get("steady_ingest_s_per_insert")
    if per:
        full = args.edgefactor << TARGET_LOG2_V
        log(f"steady ingest after compilation: {per * (1 << 20)!r} host wall "
            f"s per 2^20 inserts; the 2^{TARGET_LOG2_V}-vertex target's "
            f"{full} inserts would take {per * full!r} s")
    log(f"peak device bytes in use: {rec['peak_bytes_in_use']}")
    log(f"kernel impl: {rec['impl']}")
    log(f"apply program has tpu_custom_call: "
        f"{rec['apply_program_has_tpu_custom_call']}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev[0].platform, "kind": dev[0].device_kind,
        "count": len(dev)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
