"""Pallas kernels (interpret mode) vs pure-jnp oracles — shape/dtype sweep."""
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import sort as S
from repro.core.keys import pack_keys
from repro.core.sort import SortSpec
from repro.core.sort_optimizer import optimize_sort
from repro.kernels import ops
from repro.kernels import ref as R
from repro.kernels.append import append_pallas, append_tile_rows
from repro.kernels.compact import compact_rows_pallas, defrag_rows_pallas
from repro.kernels.frontier import frontier_pallas
from repro.kernels.sort_lookup import sort_lookup_pallas


@pytest.mark.parametrize("K,D", [(1, 8), (3, 16), (5, 64), (2, 128)])
@pytest.mark.parametrize("wdtype", [jnp.float32, jnp.bfloat16])
def test_compact_kernel_sweep(K, D, wdtype, rng):
    n_cap = 64
    dst = rng.integers(-1, n_cap, (K, D)).astype(np.int32)
    w = np.round(rng.uniform(0, 2, (K, D))).astype(np.float32)
    ts = rng.permutation(K * D).reshape(K, D).astype(np.int32)
    size = rng.integers(0, D + 1, (K,)).astype(np.int32)
    a = R.compact_rows_ref(jnp.asarray(dst), jnp.asarray(w, wdtype),
                           jnp.asarray(ts), jnp.asarray(size))
    b = compact_rows_pallas(jnp.asarray(dst), jnp.asarray(w, wdtype),
                            jnp.asarray(ts), jnp.asarray(size))
    for x, y in zip(a, b):
        np.testing.assert_allclose(np.asarray(x, np.float32),
                                   np.asarray(y, np.float32))


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2 ** 31), st.integers(1, 64))
def test_compact_kernel_read_ts(seed, size_hint):
    rng = np.random.default_rng(seed)
    K, D = 2, 32
    dst = rng.integers(-1, 32, (K, D)).astype(np.int32)
    w = np.round(rng.uniform(0, 2, (K, D))).astype(np.float32)
    ts = rng.permutation(K * D).reshape(K, D).astype(np.int32)
    size = np.minimum(size_hint, D) * np.ones(K, np.int32)
    rt = int(rng.integers(0, K * D))
    a = R.compact_rows_ref(*map(jnp.asarray, (dst, w, ts, size)), read_ts=rt)
    b = compact_rows_pallas(*map(jnp.asarray, (dst, w, ts, size)),
                            read_ts=rt)
    for x, y in zip(a, b):
        assert np.array_equal(np.asarray(x), np.asarray(y))


@pytest.mark.parametrize("n,tile", [(128, 64), (500, 128)])
def test_sort_lookup_kernel(n, tile, rng):
    cfg = optimize_sort(n, 32, 5)
    spec = SortSpec.from_config(cfg, 2 * n)
    stt = S.make_sort(spec)
    ids = rng.choice(2 ** 32, n, replace=False).astype(np.uint64)
    stt = S.insert_mappings(spec, stt, pack_keys(ids, 32),
                            jnp.arange(n, dtype=jnp.int32),
                            jnp.ones(n, bool))
    q = np.concatenate([ids, rng.choice(2 ** 32, 2 * tile - n % tile or tile)
                        .astype(np.uint64)])
    q = q[: (len(q) // tile) * tile]
    qk = pack_keys(q, 32)
    a = R.sort_lookup_ref(stt.pools, stt.counts, qk,
                          fanout_bits=spec.fanout_bits,
                          bit_offsets=spec.bit_offsets)
    b = sort_lookup_pallas(stt.pools, stt.counts, qk,
                           fanout_bits=spec.fanout_bits,
                           bit_offsets=spec.bit_offsets, tile=tile)
    assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("K,D", [(1, 8), (4, 16), (3, 64), (2, 128)])
def test_defrag_rows_kernel_sweep(K, D, rng):
    """The defrag row compactor (bitmap + prefix-popcount ranks) must match
    its oracle bit-exactly: dedup by highest occupied position, tombstones
    dropped, survivors emitted by ascending destination."""
    n_cap = 64
    dst = rng.integers(-1, n_cap, (K, D)).astype(np.int32)
    w = np.round(rng.uniform(0, 2, (K, D))).astype(np.float32)
    ts = rng.permutation(K * D).reshape(K, D).astype(np.int32)
    size = rng.integers(0, D + 1, (K,)).astype(np.int32)
    a = R.defrag_rows_ref(*map(jnp.asarray, (dst, w, ts, size)))
    b = defrag_rows_pallas(*map(jnp.asarray, (dst, w, ts, size)))
    for x, y in zip(a, b):
        assert np.array_equal(np.asarray(x), np.asarray(y))


def test_defrag_rows_keep_all_orders_by_dst_then_pos(rng):
    """'grow' mode keeps every occupied entry (dups + tombstones), grouped
    by destination in position order, and still reports live pairs."""
    dst = np.array([[3, 1, 3, 2, 1, -1]], np.int32)
    w = np.array([[1.0, 0.0, 2.0, 1.0, 5.0, 9.0]], np.float32)
    ts = np.array([[1, 2, 3, 4, 5, 6]], np.int32)
    size = np.array([5], np.int32)
    d, ww, tt, cnt, live = R.defrag_rows_ref(
        *map(jnp.asarray, (dst, w, ts, size)), keep_all=True)
    assert cnt[0] == 5 and live[0] == 3      # pairs 1, 2, 3 all end live
    assert np.asarray(d)[0, :5].tolist() == [1, 1, 2, 3, 3]
    assert np.asarray(ww)[0, :5].tolist() == [0.0, 5.0, 1.0, 1.0, 2.0]


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 2 ** 31), st.integers(2, 16))
def test_append_kernel(seed, tile):
    """Fused append (slot scatter + pre-append last-writer probe) must match
    the oracle bit-exactly: pool contents AND per-pair was_live."""
    rng = np.random.default_rng(seed)
    NB, BS, B = 32, 8, 24
    dst = rng.integers(-1, 16, (NB, BS)).astype(np.int32)
    w = np.round(rng.uniform(0, 2, (NB, BS))).astype(np.float32)
    ts = (rng.permutation(NB * BS).reshape(NB, BS) + 1).astype(np.int32)
    wblk = rng.integers(0, NB, B).astype(np.int32)
    wlane = rng.integers(0, BS, B).astype(np.int32)
    wval = rng.random(B) < 0.7
    wd = rng.integers(0, 16, B).astype(np.int32)
    ww = np.round(rng.uniform(0, 2, B)).astype(np.float32)
    wts = (rng.permutation(B) + 1000).astype(np.int32)
    pstart = rng.integers(-1, NB, B).astype(np.int32)
    psize = rng.integers(0, 3 * BS, B).astype(np.int32)
    pv = rng.integers(-1, 16, B).astype(np.int32)
    args = tuple(map(jnp.asarray, (dst, w, ts, wblk, wlane, wval, wd, ww,
                                   wts, pstart, psize, pv)))
    a = R.append_ref(*args)
    b = append_pallas(*args, tile=tile)
    for x, y in zip(a, b):
        assert np.array_equal(np.asarray(x), np.asarray(y))


def _touched_tiles(NB, BS, T, wblk, wval, pstart, psize):
    """Host replica of the edgepool touched-tile computation: probe
    extents marked as [first, last] tile ranges, landed slots as points."""
    n_tiles = NB // T
    touched = np.zeros(n_tiles, bool)
    for s, z in zip(pstart, psize):
        rows = -(-z // BS)
        if s >= 0 and rows > 0:
            touched[s // T:(s + rows - 1) // T + 1] = True
    for b, v in zip(wblk, wval):
        if v:
            touched[b // T] = True
    order = np.nonzero(touched)[0]
    n = len(order)
    tiles = np.full(n_tiles, order[-1] if n else 0, np.int32)
    tiles[:n] = order
    return tiles, n


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 2 ** 31))
def test_append_kernel_bounded_scan(seed):
    """The prefetched tile list must (a) reproduce the dense-probe oracle
    exactly — every tile a probe extent or landed slot can reach is
    visited — and (b) stay within the touched-extent bound: ops packed
    into a corner of the pool never visit the rest of it."""
    rng = np.random.default_rng(seed)
    NB, BS, B, T = 64, 8, 16, 8
    dst = rng.integers(-1, 16, (NB, BS)).astype(np.int32)
    w = np.round(rng.uniform(0, 2, (NB, BS))).astype(np.float32)
    ts = (rng.permutation(NB * BS).reshape(NB, BS) + 1).astype(np.int32)
    # ops confined to the first quarter of the pool: extents start in
    # rows [0, 8), slots land in rows [8, 16) — at/after the extent end,
    # the probe/write commutation invariant the production path upholds
    pstart = rng.integers(-1, 8, B).astype(np.int32)
    psize = rng.integers(0, BS + 1, B).astype(np.int32)
    pv = rng.integers(-1, 16, B).astype(np.int32)
    wblk = rng.integers(8, 16, B).astype(np.int32)
    wlane = rng.integers(0, BS, B).astype(np.int32)
    wval = rng.random(B) < 0.7
    wd = rng.integers(0, 16, B).astype(np.int32)
    ww = np.round(rng.uniform(0, 2, B)).astype(np.float32)
    wts = (rng.permutation(B) + 1000).astype(np.int32)

    tiles, n_touched = _touched_tiles(NB, BS, T, wblk, wval, pstart, psize)
    assert n_touched <= 2 * (16 // T)   # the touched-extent bound: 2 tiles
    args = tuple(map(jnp.asarray, (dst, w, ts, wblk, wlane, wval, wd, ww,
                                   wts, pstart, psize, pv)))
    a = R.append_ref(*args)
    b = append_pallas(*args, jnp.asarray(tiles),
                      jnp.asarray(n_touched, jnp.int32), tile=T)
    for x, y in zip(a, b):
        assert np.array_equal(np.asarray(x), np.asarray(y))


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 2 ** 31))
def test_frontier_kernel(seed):
    rng = np.random.default_rng(seed)
    NB, BS, n = 32, 8, 128
    W = n // 32
    owner = rng.integers(-1, n, NB).astype(np.int32)
    dst = rng.integers(-1, n, (NB, BS)).astype(np.int32)
    valid = rng.random((NB, BS)) < 0.5
    f = rng.integers(0, 2 ** 32, W, dtype=np.uint32)
    v = rng.integers(0, 2 ** 32, W, dtype=np.uint32)
    a = R.frontier_ref(*map(jnp.asarray, (owner, dst, valid, f, v)))
    b = frontier_pallas(*map(jnp.asarray, (owner, dst, valid, f, v)), tile=8)
    assert np.array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("platform", ["tpu", "cpu"])
def test_kernel_impl_resolves_from_one_static_table(platform, monkeypatch):
    """'auto' follows the backend: the static TPU table on a TPU, the jnp
    references elsewhere; explicit knobs always win, and Pallas runs in
    interpret mode exactly off-TPU."""
    monkeypatch.setattr(ops, "platform", lambda: platform)
    for kernel, tpu_impl in ops.TPU_IMPL.items():
        want = tpu_impl if platform == "tpu" else "ref"
        assert ops.resolve(kernel) == want
        assert ops.resolve(kernel, "pallas") == "pallas"
        assert ops.resolve(kernel, "ref") == "ref"
    assert ops.interpret() == (platform != "tpu")
    with pytest.raises(ValueError):
        ops.resolve("append", "mosaic")
