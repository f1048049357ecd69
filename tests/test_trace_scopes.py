"""The store's own trace instrumentation on the CPU: the named scopes of
its jitted programs, the ``graph.*`` host spans of its calls, and the
host-clock counters beside them."""
import pathlib
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import AnalyticsOp, OpBatch, ReadOp, make_store
from repro.core import radixgraph as rg
from repro.core.keys import pack_keys

B = 32
_OP = re.compile(r"^\s*(?:ROOT )?%\S+ = .*? (gather|scatter|sort)\(")
_NAME = re.compile(r'op_name="([^"]*)"')


@pytest.fixture(scope="module")
def graph():
    g = rg.RadixGraph(n_max=64, batch=B, pool_blocks=256, k_max=8, k_big=2,
                      dmax=64, probe_width=16, undirected=True)
    rng = np.random.default_rng(0)
    g.apply_ops(rng.integers(0, 48, 200).astype(np.uint64),
                rng.integers(0, 48, 200).astype(np.uint64),
                np.ones(200, np.float32))
    return g


def _programs(g):
    keys = pack_keys(np.arange(B, dtype=np.uint64), 32)
    w, m = jnp.ones((B,), jnp.float32), jnp.ones((B,), bool)
    ss, ps = g.sort_spec, g.pool_spec
    return {
        "step_update_edges": (
            jax.jit(rg.step_update_edges, static_argnums=(0, 1)),
            (ss, ps, g.state, keys, keys, w, m),
            {"vertex_index", "group", "compact", "probe", "append"}),
        "step_update_edges_pipelined": (
            jax.jit(rg.step_update_edges_pipelined, static_argnums=(0, 1)),
            (ss, ps, g.state, keys[None], keys[None], w[None], m[None]),
            {"vertex_index", "group", "compact", "probe", "append"}),
        "step_neighbors": (
            jax.jit(rg.step_neighbors, static_argnums=(0, 1, 4)),
            (ss, ps, g.state, keys, 64), {"vertex_index", "gather",
                                          "compact"}),
        "step_snapshot": (
            jax.jit(rg.step_snapshot, static_argnums=(0, 1, 2)),
            (ss, ps, 512, g.state), {"live_edges", "csr"}),
    }


@pytest.mark.parametrize("name", ["step_update_edges",
                                  "step_update_edges_pipelined",
                                  "step_neighbors", "step_snapshot"])
def test_every_gather_scatter_and_sort_sits_in_a_declared_scope(graph,
                                                                name):
    fn, args, scopes = _programs(graph)[name]
    hlo = fn.lower(*args).compile().as_text()
    seen, found = set(), 0
    for line in hlo.splitlines():
        op = _OP.match(line)
        if not op:
            continue
        found += 1
        path = _NAME.search(line)
        assert path, f"{op.group(1)} without op_name: {line[:160]}"
        parts = path.group(1).split("/")
        assert parts[0] == f"jit({name})", parts
        # the scan twin's body runs under while/body/closed_call/ first
        first = [p for p in parts[1:]
                 if p not in ("while", "body", "closed_call")][0]
        assert first in scopes, f"{op.group(1)} under {path.group(1)!r}"
        seen.add(first)
    assert found and seen == scopes, (found, seen)


def test_defrag_is_named_inside_compact(graph):
    fn, args, _ = _programs(graph)["step_update_edges"]
    text = fn.lower(*args).as_text(debug_info=True)
    assert re.search(r"step_update_edges\)/compact/cond/branch_\d+_fun/"
                     r"defrag/", text)


def _events(tdir):
    from jax.profiler import ProfileData
    path = sorted(pathlib.Path(tdir).rglob("*.xplane.pb"))[-1]
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("graph."):
                    out.append((e.start_ns, e.start_ns + e.duration_ns,
                                e.name, dict(e.stats).get("req")))
    return out


NESTED = {
    "graph.apply": ["graph.apply.stage", "graph.apply.dispatch",
                    "graph.apply.wait"],
    "graph.read": ["graph.read.dispatch", "graph.read.wait",
                   "graph.read.fetch"],
    "graph.analytics": ["graph.analytics.snapshot", "graph.analytics.run",
                        "graph.analytics.result"],
    "graph.capture": [],
}


def _store():
    return make_store("local", n_max=96, expected_n=64, batch=64,
                      pool_blocks=1024, k_max=16, dmax=64, undirected=True)


def test_store_calls_record_nested_spans_with_their_request(tmp_path):
    store = _store()
    ids = np.arange(1, 41, dtype=np.uint64)
    rng = np.random.default_rng(1)
    batch = OpBatch.edges(ids[rng.integers(0, 40, 300)],
                          ids[rng.integers(0, 40, 300)],
                          np.ones(300, np.float32))
    store.apply(batch)                   # compiles outside the trace
    store.read(ReadOp("neighbors", ids=ids[:8]))
    store.analytics(AnalyticsOp("pagerank", {"iters": 3}))
    jax.profiler.start_trace(str(tmp_path))
    store.apply(batch)
    ep = store.capture()
    store.read(ReadOp("neighbors", ids=ids[:8]), at=ep)
    store.analytics(AnalyticsOp("pagerank", {"iters": 3}), at=ep)
    jax.profiler.stop_trace()
    ev = _events(tmp_path)
    tops = [e for e in ev if e[2] in NESTED]
    assert [e[2] for e in sorted(tops)] == ["graph.apply", "graph.capture",
                                            "graph.read", "graph.analytics"]
    assert len({e[3] for e in tops}) == 4          # one request number each
    for s, t, name, req in tops:
        assert isinstance(req, int) and req > 0
        inner = sorted(e for e in ev if e[2].startswith(name + ".")
                       and s <= e[0] and e[1] <= t)
        assert [e[2] for e in inner] == NESTED[name], name
        assert all(e[3] == req for e in inner)
    assert len(ev) == 4 + sum(len(v) for v in NESTED.values())


def test_counters_count_as_defined():
    store = _store()
    ids = np.arange(1, 41, dtype=np.uint64)
    assert "device_sync_ms" not in store.stats
    assert not hasattr(store.graph, "defrag_batches")
    assert not hasattr(store.graph, "pipe_sync_ms")
    store.apply(OpBatch.edges(ids[:20], ids[20:], np.ones(20, np.float32)))
    assert store.stats["flushes"] == 1
    assert store.stats["flush_wait_ms"] == round(store.graph.pipe_wait_ms, 3)
    assert store.stats["flush_wait_ms"] >= 0
    assert store.stats["read_fetch_ms"] == 0
    assert store.stats["read_fetch_bytes"] == 0
    store.read(ReadOp("num_edges"))             # a counter, no copy
    assert store.stats["read_fetch_bytes"] == 0
    g = store.graph
    n_cap = np.asarray(g.state.vt.ids).shape[0]
    dmax = g.pool_spec.dmax
    store.read(ReadOp("neighbors", ids=ids[:8]))
    per_batch = g.batch * dmax * 8 + g.batch * 4
    assert store.stats["read_fetch_bytes"] == per_batch + n_cap * 8
    store.read(ReadOp("degree", ids=np.arange(1, 71, dtype=np.uint64)))
    # 70 IDs: two key batches
    assert store.stats["read_fetch_bytes"] == \
        per_batch + n_cap * 8 + 2 * g.batch * 4
    assert store.stats["read_fetch_ms"] > 0


def test_sharded_store_renames_its_wait():
    store = make_store("sharded", n_shards=1, n_per_shard=64, batch=64,
                       pool_blocks=256, k_max=16, dmax=64)
    ids = np.arange(1, 41, dtype=np.uint64)
    assert "device_sync_ms" not in store.stats
    store.apply(OpBatch.edges(ids[:20], ids[20:], np.ones(20, np.float32)))
    assert store.stats["flushes"] == 1 and store.stats["flush_wait_ms"] >= 0
