"""The snapshot's one carrying sort gives the same CSR, bit for bit, as a
lexsort of the pool followed by gathers of its entries by the permutation."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import radixgraph as rg

INT_MAX = np.iinfo(np.int32).max


@functools.partial(jax.jit, static_argnums=(0, 1))
def _reference(pspec, m_cap, state, read_ts=None):
    """Live edges by permutation (lexsort, then a gather of each array by
    its order), then the CSR steps of ``step_snapshot``."""
    pool, vt = state.pool, state.vt
    bs = pspec.block_size
    n_cap = vt.size.shape[0]
    N = pool.dst.shape[0] * bs
    own = jnp.repeat(pool.owner, bs)
    d = pool.dst.reshape(-1)
    w = pool.weight.reshape(-1)
    t = pool.ts.reshape(-1)
    blk = jnp.arange(N, dtype=jnp.int32) // bs
    lane = jnp.arange(N, dtype=jnp.int32) % bs
    ownc = jnp.clip(own, 0, n_cap - 1)
    pos = (blk - vt.start_block[ownc]) * bs + lane
    valid = ((own >= 0) & (pos >= 0) & (pos < vt.size[ownc])
             & (vt.del_time[ownc] == 0)
             & (d >= 0) & (vt.del_time[jnp.clip(d, 0, n_cap - 1)] == 0))
    if read_ts is not None:
        valid = valid & (t <= read_ts)
    so = jnp.where(valid, own, INT_MAX)
    sd = jnp.where(valid, d, INT_MAX)
    stv = jnp.where(valid, t, 0)
    order = jnp.lexsort((stv, sd, so))
    so, sd, sw = so[order], sd[order], w[order]
    nxt_o = jnp.concatenate([so[1:], jnp.full((1,), -2, so.dtype)])
    nxt_d = jnp.concatenate([sd[1:], jnp.full((1,), -2, sd.dtype)])
    keep = ((so != nxt_o) | (sd != nxt_d)) & (so < INT_MAX) & (sw != 0)
    m = jnp.sum(keep.astype(jnp.int32))
    counts = jnp.zeros((n_cap,), jnp.int32).at[
        jnp.where(keep, so, n_cap)].add(1, mode="drop")
    indptr = jnp.concatenate([jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)])
    tgt = jnp.where(keep, jnp.cumsum(keep.astype(jnp.int32)) - 1, m_cap)
    dst = jnp.full((m_cap,), -1, jnp.int32).at[tgt].set(sd, mode="drop")
    wgt = jnp.zeros((m_cap,), jnp.float32).at[tgt].set(sw, mode="drop")
    return indptr, dst, wgt, m


def _store(undirected, seed):
    """A seeded store whose pool holds every kind of entry the snapshot
    resolves, and the timestamp of a past version of it."""
    g = rg.RadixGraph(n_max=128, key_bits=16, expected_n=64, batch=64,
                      pool_blocks=2048, block_size=8, dmax=256, k_max=32,
                      undirected=undirected)
    rng = np.random.default_rng(seed)
    ids = rng.choice(2 ** 16, 48, replace=False).astype(np.uint64)
    src, dst = ids[rng.integers(0, 48, 300)], ids[rng.integers(0, 48, 300)]
    g.apply_ops(src, dst, rng.uniform(0.5, 2, 300).astype(np.float32))
    # repeated writes to one pair, within one batch and across batches
    pair = np.full(5, ids[0]), np.full(5, ids[1])
    g.apply_ops(*pair, np.arange(1, 6, dtype=np.float32))
    past = g.current_ts
    g.apply_ops(*pair, np.float32([7, 0, 9, 11, 13]))
    # weight updates and deletes (weight 0) of loaded pairs
    w = rng.uniform(0.5, 2, 300).astype(np.float32)
    w[rng.random(300) < 0.25] = 0
    g.apply_ops(src, dst, w)
    g.delete_vertices(ids[2:6])
    g.apply_ops(ids[rng.integers(0, 48, 100)], ids[rng.integers(0, 48, 100)],
                rng.uniform(0.5, 2, 100).astype(np.float32))
    return g, past


@pytest.mark.parametrize("undirected", [False, True])
@pytest.mark.parametrize("when", ["live", "past"])
def test_snapshot_matches_the_lexsort_and_gather_reference(undirected, when):
    g, past = _store(undirected, seed=11 + undirected)
    read_ts = None if when == "live" else past
    m_cap = g.pool_spec.capacity_entries
    snap = rg._snapshot(g.sort_spec, g.pool_spec, m_cap, g.state, read_ts)
    indptr, dst, wgt, m = _reference(g.pool_spec, m_cap, g.state, read_ts)
    assert int(snap.m) == int(m) > 0
    np.testing.assert_array_equal(np.asarray(snap.indptr), np.asarray(indptr))
    np.testing.assert_array_equal(np.asarray(snap.dst), np.asarray(dst))
    np.testing.assert_array_equal(np.asarray(snap.weight).view(np.uint32),
                                  np.asarray(wgt).view(np.uint32))
