"""Snapshot-log edge storage vs an ordered Python oracle — the paper's core
semantics (insert/update/delete, compaction, MVCC) under property testing."""
import collections

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import edgepool as ep
from repro.core.radixgraph import RadixGraph


def mk(policy="snaplog", **kw):
    args = dict(n_max=256, key_bits=16, expected_n=64, batch=128,
                pool_blocks=4096, block_size=8, dmax=512, k_max=32,
                policy=policy)
    args.update(kw)
    return RadixGraph(**args)


ops_strategy = st.lists(
    st.tuples(st.integers(0, 30), st.integers(0, 30),
              st.sampled_from([0.0, 1.0, 2.5])),
    min_size=1, max_size=300)


@settings(max_examples=12, deadline=None)
@pytest.mark.parametrize("policy", ["snaplog", "grow", "sorted"])
@given(ops=ops_strategy)
def test_mixed_stream_matches_oracle(policy, ops):
    g = mk(policy)
    src = np.array([o[0] for o in ops], np.uint64)
    dst = np.array([o[1] for o in ops], np.uint64)
    w = np.array([o[2] for o in ops], np.float32)
    g.apply_ops(src, dst, w)
    oracle = {}
    for s, d, ww in ops:
        if ww == 0.0:
            oracle.pop((s, d), None)
        else:
            oracle[(s, d)] = ww
    assert g.num_edges == len(oracle)
    assert not g.overflowed
    for vid in sorted({o[0] for o in ops})[:8]:
        nb_ids, nb_w = g.neighbors([vid])[0]
        got = dict(zip(nb_ids.tolist(), nb_w.tolist()))
        exp = {d: ww for (s, d), ww in oracle.items() if s == vid}
        assert set(got) == set(exp)
        for k in exp:
            assert got[k] == pytest.approx(exp[k])


@settings(max_examples=6, deadline=None)
@pytest.mark.parametrize("policy", ["snaplog", "grow", "sorted"])
@given(ops=ops_strategy, chunk=st.sampled_from([7, 64]))
def test_rows_past_next_block_stay_empty(policy, ops, chunk):
    """New extents are never initialized: every write lands below
    ``next_block``, so the rows past it stay as a fresh pool holds them —
    through appends, compactions, vertex deletes and rebuilds."""
    g = mk(policy, pool_blocks=512, dmax=64, k_max=4)

    def check():
        pool = g.state.pool
        nxt = int(pool.next_block)
        assert (np.asarray(pool.dst)[nxt:] == -1).all()
        assert (np.asarray(pool.weight)[nxt:] == 0).all()
        assert (np.asarray(pool.ts)[nxt:] == 0).all()

    for lo in range(0, len(ops), chunk):
        part = ops[lo:lo + chunk]
        g.apply_ops(np.array([o[0] for o in part], np.uint64),
                    np.array([o[1] for o in part], np.uint64),
                    np.array([o[2] for o in part], np.float32))
        check()
    g.delete_vertices([ops[0][0]])
    g.defrag()
    check()


def test_compaction_triggers_and_preserves(rng):
    g = mk(dmax=256)
    # hammer a single vertex with updates so compaction fires repeatedly
    dsts = rng.integers(0, 40, 600).astype(np.uint64)
    ws = rng.uniform(1, 2, 600).astype(np.float32)
    g.apply_ops(np.zeros(600, np.uint64), dsts, ws)
    oracle = {}
    for d, w in zip(dsts, ws):
        oracle[int(d)] = float(w)
    ids, w = g.neighbors([0])[0]
    got = dict(zip(ids.tolist(), w.tolist()))
    assert set(got) == set(oracle)
    # capacity discipline: cap <= 2 * ceil(live/bs) * bs + slack blocks
    off = int(g.lookup(np.array([0], np.uint64))[0])
    cap = int(g.state.vt.cap[off])
    live = len(oracle)
    assert cap <= 4 * max(live, 8)


def test_mvcc_read_ts(rng):
    g = mk()
    g.apply_ops(np.array([1, 1], np.uint64), np.array([2, 3], np.uint64),
                np.array([1.0, 1.0], np.float32))
    ts1 = g.current_ts
    g.apply_ops(np.array([1, 1], np.uint64), np.array([2, 4], np.uint64),
                np.array([0.0, 5.0], np.float32))  # delete (1,2), add (1,4)
    # current view
    ids, w = g.neighbors([1])[0]
    assert set(ids.tolist()) == {3, 4}
    # historical view at ts1: (1,2) alive, (1,4) absent
    ids, w = g.neighbors([1], read_ts=ts1)[0]
    assert set(ids.tolist()) == {2, 3}


def test_vertex_delete_hides_edges_and_recycles(rng):
    g = mk()
    g.apply_ops(np.array([1, 2, 3], np.uint64), np.array([2, 3, 1], np.uint64),
                np.array([1, 1, 1], np.float32))
    g.delete_vertices([2])
    assert g.lookup(np.array([2], np.uint64))[0] == -1
    # edges from/to 2 invisible
    assert g.num_edges == 1  # only (3,1)
    ids, _ = g.neighbors([1])[0]
    assert ids.tolist() == []
    # defrag recycles the row; re-adding works
    g.defrag()
    g.add_vertices([2])
    assert g.lookup(np.array([2], np.uint64))[0] >= 0
    assert g.num_edges == 1


def test_defrag_is_semantic_noop(rng):
    g = mk()
    src = rng.integers(0, 30, 500).astype(np.uint64)
    dst = rng.integers(0, 30, 500).astype(np.uint64)
    w = rng.uniform(0.5, 2, 500).astype(np.float32)
    w[rng.random(500) < 0.2] = 0
    g.apply_ops(src, dst, w)
    before = {tuple(x) for x in np.stack(
        [np.asarray(g.snapshot().dst)[:g.num_edges]]).T.tolist()}
    m0 = g.num_edges
    g.defrag()
    assert g.num_edges == m0
    snap = g.snapshot()
    after = {tuple(x) for x in np.stack(
        [np.asarray(snap.dst)[:m0]]).T.tolist()}
    assert before == after


def test_amortized_o1_defrag_count(rng):
    """Theorem 2 proxy: the number of defrags grows logarithmically, not
    linearly, with the op count (the pool's ``defrags`` counter is exact —
    each global rebuild increments it once)."""
    g = mk(pool_blocks=2048)
    n_batches = 0
    for wave in range(8):
        src = rng.integers(0, 50, 256).astype(np.uint64)
        dst = rng.integers(0, 50, 256).astype(np.uint64)
        w = rng.uniform(0.5, 2, 256).astype(np.float32)
        g.apply_ops(src, dst, w)
        n_batches += 1
    assert not g.overflowed
    # far fewer rebuilds than batches (2x capacity growth => O(log d) per
    # vertex); an explicit defrag() adds exactly one
    assert g.num_defrags < n_batches
    before = g.num_defrags
    g.defrag()
    assert g.num_defrags == before + 1


# --------------------------------------------------------------------------
# mixed streams with undirected=True: the interleaved directions must
# preserve stream order (op i's two orientations land at timestamps 2i, 2i+1)
# --------------------------------------------------------------------------

def _undirected_oracle(ops):
    oracle = {}
    for s, d, w in ops:
        for a, b in ((int(s), int(d)), (int(d), int(s))):
            if w == 0.0:
                oracle.pop((a, b), None)
            else:
                oracle[(a, b)] = float(w)
    return oracle


def _check_against_oracle(g, oracle, vids):
    assert g.num_edges == len(oracle)
    for vid in vids:
        nb_ids, nb_w = g.neighbors([vid])[0]
        got = dict(zip(nb_ids.tolist(), nb_w.tolist()))
        exp = {b: w for (a, b), w in oracle.items() if a == vid}
        assert got.keys() == exp.keys(), (vid, got, exp)
        for k in exp:
            assert got[k] == pytest.approx(exp[k])


def test_mixed_stream_undirected_interleaved_order():
    g = mk(undirected=True)
    # one batch exercising every ordering hazard:
    #  - update through the REVERSE orientation (op 2 overwrites op 0's edge)
    #  - delete after update (op 3 kills both directions of (1,2))
    #  - delete through the reverse orientation (op 5 kills op 4's edge)
    #  - re-insert after delete of the same pair (op 6)
    #  - self-loop (ops 2i/2i+1 collapse to one entry)
    ops = [(1, 2, 1.0), (3, 1, 2.0), (2, 1, 5.0), (1, 2, 0.0),
           (4, 2, 1.5), (2, 4, 0.0), (1, 2, 3.0), (2, 3, 7.0), (5, 5, 9.0)]
    g.apply_ops(np.array([o[0] for o in ops], np.uint64),
                np.array([o[1] for o in ops], np.uint64),
                np.array([o[2] for o in ops], np.float32))
    oracle = _undirected_oracle(ops)
    assert oracle == {(1, 2): 3.0, (2, 1): 3.0, (1, 3): 2.0, (3, 1): 2.0,
                      (2, 3): 7.0, (3, 2): 7.0, (5, 5): 9.0}
    _check_against_oracle(g, oracle, [1, 2, 3, 4, 5])
    assert not g.overflowed and g.dropped_ops == 0


# --------------------------------------------------------------------------
# live-edge accounting on the probe-free ingest fast path
# --------------------------------------------------------------------------

live_ops_strategy = st.lists(
    st.tuples(st.integers(0, 20), st.integers(0, 20),
              st.sampled_from([0.0, 0.0, 1.0, 2.5, 7.0])),
    min_size=1, max_size=120)


@settings(max_examples=15, deadline=None)
@given(ops=live_ops_strategy, chunk=st.integers(1, 60))
def test_live_counter_exact_on_in_window_streams(ops, chunk):
    """Property: across mixed insert/update/delete streams applied in
    arbitrary chunkings, ``live_m`` stays EXACT (never dirty) while every
    touched vertex fits the probe window, and matches a full recount."""
    g = mk(probe_width=64)
    for lo in range(0, len(ops), chunk):
        part = ops[lo:lo + chunk]
        g.apply_ops(np.array([o[0] for o in part], np.uint64),
                    np.array([o[1] for o in part], np.uint64),
                    np.array([o[2] for o in part], np.float32))
    oracle = {}
    for s, d, ww in ops:
        if ww == 0.0:
            oracle.pop((s, d), None)
        else:
            oracle[(s, d)] = ww
    assert int(g.state.pool.live_dirty) == 0
    assert int(g.state.pool.live_m) == len(oracle)
    assert g.num_edges == len(oracle)
    assert g.num_edges == int(g.snapshot().m)   # vs full rebuild
    assert not g.overflowed


def test_bounded_probe_flags_dirty_past_window():
    """A probed pair whose owner outgrew the probe WINDOW (and was not
    compacted this batch) must flag the counter dirty — the newest entry
    may sit past the window — and the recount must heal it."""
    g = mk(probe_width=16, dmax=256)
    g.apply_ops(np.zeros(20, np.uint64), np.arange(1, 21, dtype=np.uint64),
                np.ones(20, np.float32))
    assert int(g.state.pool.live_dirty) == 0    # first touch: probe size 0
    assert g.num_edges == 20
    # update one pair: pre-append size (20) > window (16), no compaction
    g.apply_ops(np.zeros(1, np.uint64), np.array([5], np.uint64),
                np.array([2.0], np.float32))
    assert int(g.state.pool.live_dirty) == 1
    assert g.num_edges == 20                    # recount, not 21
    assert int(g.state.pool.live_dirty) == 0    # written back


def test_compaction_fold_keeps_over_window_vertex_exact():
    """A vertex past the probe window that IS compacted in the same batch
    hands the probe its liveness fold: the counter stays exact (no dirty)
    even though the window alone could not decide pair liveness."""
    g = mk(probe_width=16, dmax=256)
    g.apply_ops(np.zeros(20, np.uint64), np.arange(1, 21, dtype=np.uint64),
                np.ones(20, np.float32))
    # cap is now 24 (3 blocks of 8): 5 incoming ops overflow -> tier-L
    # compaction of vertex 0 (size 20 > window 16) with fold
    ops_d = np.array([3, 5, 21, 22, 4], np.uint64)
    ops_w = np.array([9.0, 0.0, 1.0, 1.0, 9.0], np.float32)
    g.apply_ops(np.zeros(5, np.uint64), ops_d, ops_w)
    assert int(g.state.pool.live_dirty) == 0
    # 20 - 1 delete + 2 inserts = 21, updates don't change the count
    assert int(g.state.pool.live_m) == 21
    assert g.num_edges == int(g.snapshot().m) == 21


@pytest.mark.parametrize("impl", ["pallas", "ref"])
def test_explicit_impl_reaches_kernel_dispatch(impl, monkeypatch):
    """``append_impl`` / ``compact_impl`` set on the graph decide which
    kernel the jitted step, the reads and the rebuild trace — on any
    backend — and the dense append oracle never serves the store."""
    from repro.kernels import append as ka, compact as kc, ref as kr
    calls = collections.Counter()
    for mod, name in ((ka, "append_pallas"), (kc, "compact_rows_pallas"),
                      (kc, "defrag_rows_pallas"), (kr, "append_ref")):
        def spy(*a, _fn=getattr(mod, name), _name=name, **k):
            calls[_name] += 1
            return _fn(*a, **k)
        monkeypatch.setattr(mod, name, spy)
    g = RadixGraph(n_max=80, key_bits=16, expected_n=40, batch=32,
                   pool_blocks=136, block_size=8, dmax=64, k_max=8,
                   append_impl=impl, compact_impl=impl)
    dst = np.arange(1, 41, dtype=np.uint64)
    g.add_edges(np.zeros(20, np.uint64), dst[:20])
    g.add_edges(np.zeros(20, np.uint64), dst[20:])   # vertex 0 overflows
    g.defrag()
    got = g.neighbors([0])[0][0]
    assert sorted(got.tolist()) == dst.tolist()
    assert calls["append_ref"] == 0
    for name in ("append_pallas", "compact_rows_pallas",
                 "defrag_rows_pallas"):
        assert (calls[name] > 0) == (impl == "pallas"), (name, calls)


def test_pallas_append_path_matches_ref_path(rng):
    """The fused Pallas append kernel (interpret mode) drives the same
    graph evolution as the jnp scatter + windowed probe path — and its
    full-extent probe never flags the counter dirty."""
    ids = rng.integers(0, 12, (150, 2)).astype(np.uint64)
    ws = rng.uniform(0.5, 2, 150).astype(np.float32)
    ws[rng.random(150) < 0.3] = 0.0
    kw = dict(n_max=64, key_bits=16, expected_n=32, batch=32,
              pool_blocks=128, block_size=8, dmax=64, k_max=8)
    g_ref = RadixGraph(**kw)
    g_pal = RadixGraph(append_impl="pallas", **kw)
    for lo in range(0, 150, 50):
        for g in (g_ref, g_pal):
            g.apply_ops(ids[lo:lo + 50, 0], ids[lo:lo + 50, 1],
                        ws[lo:lo + 50])
    assert int(g_pal.state.pool.live_dirty) == 0
    assert g_ref.num_edges == g_pal.num_edges
    assert np.array_equal(np.asarray(g_ref.snapshot().dst),
                          np.asarray(g_pal.snapshot().dst))
    for vid in range(12):
        a = g_ref.neighbors([vid])[0]
        b = g_pal.neighbors([vid])[0]
        assert set(a[0].tolist()) == set(b[0].tolist())


# --------------------------------------------------------------------------
# streaming defrag: bit-identical to the dense entry-scatter rebuild
# --------------------------------------------------------------------------

defrag_ops_strategy = st.lists(
    st.tuples(st.integers(0, 25), st.integers(0, 25),
              st.sampled_from([0.0, 1.0, 2.5])),
    min_size=1, max_size=250)

# one compile per pool config, shared across property examples
import jax  # noqa: E402

_stream_defrag = jax.jit(ep.defrag, static_argnums=0)
_dense_defrag = jax.jit(ep._defrag_dense, static_argnums=0)


def _assert_states_equal(a, b, ctx):
    pa, va = a
    pb, vb = b
    for name in pa._fields:
        assert np.array_equal(np.asarray(getattr(pa, name)),
                              np.asarray(getattr(pb, name))), (ctx, name)
    for name in va._fields:
        assert np.array_equal(np.asarray(getattr(va, name)),
                              np.asarray(getattr(vb, name))), (ctx, name)


@settings(max_examples=8, deadline=None)
@pytest.mark.parametrize("policy", ["snaplog", "grow", "sorted"])
@given(ops=defrag_ops_strategy, dele=st.lists(st.integers(0, 25), max_size=3),
       inc_v=st.lists(st.tuples(st.integers(0, 25), st.integers(1, 40)),
                      max_size=4))
def test_streaming_defrag_bit_identical_to_dense(policy, ops, dele, inc_v):
    """Property: across policies, tombstones, deleted vertices, and
    arbitrary pending-incoming hints, the streaming block-row rebuild
    produces the SAME pool and vertex table — every array, including the
    ``live_m`` resync — as the dense entry-scatter reference."""
    g = mk(policy)
    src = np.array([o[0] for o in ops], np.uint64)
    dst = np.array([o[1] for o in ops], np.uint64)
    w = np.array([o[2] for o in ops], np.float32)
    g.apply_ops(src, dst, w)
    if dele:
        g.delete_vertices(np.unique(np.array(dele, np.uint64)))
    incoming = np.zeros((g.n_max,), np.int32)
    for vid, cnt in inc_v:
        off = int(g.lookup(np.array([vid], np.uint64))[0])
        if off >= 0:
            incoming[off] += cnt
    pool, vt = g.state.pool, g.state.vt
    inc = jnp.asarray(incoming)
    stream = _stream_defrag(g.pool_spec, pool, vt, inc)
    dense = _dense_defrag(g.pool_spec, pool, vt, inc)
    _assert_states_equal(stream, dense, policy)
    # the rebuild is the live counter's resync point: exact, not dirty
    assert int(stream[0].live_dirty) == 0
    g.defrag()
    assert g.num_edges == int(g.snapshot().m)


def test_streaming_defrag_falls_back_past_dmax(rng):
    """A vertex grown past dmax (post-jumbo) cannot ride the size
    segments: the dispatcher must fall back to the dense rebuild and
    still produce the identical state."""
    g = mk(dmax=64, k_max=8, k_big=2, pool_blocks=8192)
    # one vertex with > dmax distinct live edges: jumbo batches rebuild
    # it via defrag, after which size (= live degree) exceeds dmax
    dsts = np.arange(1, 101, dtype=np.uint64)
    g.apply_ops(np.zeros(100, np.uint64), dsts, np.ones(100, np.float32))
    off = int(g.lookup(np.array([0], np.uint64))[0])
    assert int(g.state.vt.size[off]) > 64
    pool, vt = g.state.pool, g.state.vt
    inc = jnp.zeros((g.n_max,), jnp.int32)
    _assert_states_equal(_stream_defrag(g.pool_spec, pool, vt, inc),
                         _dense_defrag(g.pool_spec, pool, vt, inc),
                         "past-dmax")
    ids, _ = g.neighbors([0], width=128)[0]
    assert set(ids.tolist()) == set(range(1, 101))


def test_defrag_pending_hint_presizes_extents():
    """An explicit defrag given the pending batch's sources must pre-size
    the rebuilt extents so the batch then rides the fast path; without
    the hint the same batch immediately re-overflows into a second
    rebuild (the hub-stream failure mode the hint exists for)."""
    def build():
        # batch covers the whole follow-up stream so every hub overflows
        # in ONE device batch (6 > k_max forces the rebuild fallback)
        g = mk(k_max=4, k_big=2, batch=256)
        hubs = np.arange(6, dtype=np.uint64)
        src = np.repeat(hubs, 16)
        dst = np.tile(np.arange(100, 116, dtype=np.uint64), 6)
        g.apply_ops(src, dst, np.ones(96, np.float32))
        return g, hubs
    # the follow-up batch: 40 fresh edges per hub — more than the 2d
    # discipline reserves, and 6 overflowing hubs exceed k_max/k_big
    g, hubs = build()
    src2 = np.repeat(hubs, 40)
    dst2 = np.tile(np.arange(200, 240, dtype=np.uint64), 6)
    w2 = np.ones(240, np.float32)

    g.defrag(pending_src=src2)          # hint: pre-size for the batch
    d0 = g.num_defrags
    g.apply_ops(src2, dst2, w2)
    assert g.num_defrags == d0          # no re-overflow rebuild
    assert g.dropped_ops == 0 and not g.overflowed

    g2, _ = build()
    g2.defrag()                         # control: no hint
    d0 = g2.num_defrags
    g2.apply_ops(src2, dst2, w2)
    assert g2.num_defrags == d0 + 1     # immediate re-overflow rebuild
    assert g.num_edges == g2.num_edges


def test_append_tiles_scanned_bounded_by_touched_extents():
    """The bounded append's tile counter must track the batches'
    footprints: a tiny graph in a huge pool (32 tiles) scans a handful of
    tiles per batch, never batches x pool tiles."""
    g = mk()                    # pool_blocks=4096, bs=8 -> 32 append tiles
    rng = np.random.default_rng(0)
    n_batches = 6
    for i in range(n_batches):
        src = rng.integers(0, 16, 50).astype(np.uint64)
        dst = rng.integers(0, 16, 50).astype(np.uint64)
        g.apply_ops(src, dst, np.ones(50, np.float32))
    assert g.tiles_scanned >= n_batches          # every batch lands slots
    assert g.tiles_scanned <= 4 * n_batches      # touched-extent bound
    # dense scanning would have cost batches x n_tiles
    assert g.tiles_scanned < n_batches * 32


def test_mixed_stream_undirected_order_across_batches(rng):
    """Same-pair churn split across apply_ops calls (and batch-pad
    boundaries): the global clock must keep the interleaved directions
    ordered."""
    g = mk(undirected=True, batch=64)
    ids = rng.integers(0, 16, (400, 2)).astype(np.uint64)
    ws = rng.uniform(0.5, 2, 400).astype(np.float32)
    ws[rng.random(400) < 0.3] = 0.0
    all_ops = [(int(s), int(d), float(w))
               for (s, d), w in zip(ids, ws)]
    for lo in range(0, 400, 100):  # 4 calls, each multiple padded batches
        chunk = all_ops[lo:lo + 100]
        g.apply_ops(np.array([o[0] for o in chunk], np.uint64),
                    np.array([o[1] for o in chunk], np.uint64),
                    np.array([o[2] for o in chunk], np.float32))
    oracle = _undirected_oracle(all_ops)
    _check_against_oracle(g, oracle, sorted({o[0] for o in all_ops}))
    assert not g.overflowed and g.dropped_ops == 0
