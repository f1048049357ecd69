"""Pallas TPU kernel: fused edge-pool append (ingest fast path).

One grid step owns one TOUCHED tile of pool block-rows resident in VMEM —
the tile list is a scalar-prefetch argument (``PrefetchScalarGridSpec``),
computed per batch from the owner extents the batch's probes span plus the
block rows its slots land in, so the kernel never scans tiles no op can
reach. The grid length stays static (one step per pool tile) but steps past
``n_touched`` revisit the last touched tile and skip all work: with the
revisiting-window pipeline that is zero DMA and zero compute, so a batch's
cost is O(touched_tiles x B) instead of the old O(pool_tiles x B) full-pool
scan. Each visited step makes a single pass that fuses the three stages the
XLA path runs separately:

1. **probe** — for every distinct (owner, dst) pair of the batch, scan the
   owner's extent rows that fall inside this tile for the pair's newest
   entry (last-writer-wins by timestamp), accumulating (best_ts, best_w) in
   SMEM across tiles. Because appends only claim slots at/after the
   owner's pre-batch size, probing bounded by ``psize`` commutes with the
   writes of the same tile;
2. **slot scatter** — land every op's (dst, weight, ts) at its claimed slot
   (block, lane) when the slot falls inside the tile — the batched analogue
   of the paper's ``fetch_add`` log append, one pass for all three payloads
   instead of three XLA scatters;
3. **liveness** — the per-pair (best_ts, best_w) accumulators leave the
   kernel, and ``was_live = (best_ts > 0) & (best_w != 0)`` is the exact
   pre-batch pair liveness that drives the O(1) ``live_m`` counter with NO
   bounded-window blind spot.

The pool payloads alias their outputs (``input_output_aliases``), so tiles
the batch never touches keep their contents without ever moving through
VMEM. TPU grids are sequential, so accumulating into the whole-array SMEM
outputs across grid steps is legal. The op vectors live in SMEM because the
loops read them one scalar at a time; pool rows are read and updated as
(1, BS) vectors. Validated in interpret mode against
``ref.append_ref``, which itself matches the ``_scatter_entries`` +
dense-probe semantics under the probe/write commutation invariant above.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ops

__all__ = ["append_pallas", "append_tile_rows"]


def append_tile_rows(nb: int, tile: int = 128) -> int:
    """The tile height (pool block rows per grid step) the append kernel
    uses for an ``nb``-row pool — shared with the host-side touched-tile
    computation so the prefetched tile indices mean the same thing."""
    tile = min(tile, nb)
    while nb % tile:
        tile //= 2
    return tile


def _append_kernel(tiles, n_touched, dp, wp, tp, wblk, wlane, wval, wd, ww,
                   wts, pstart, psize, pv, od, ow, ot, best_ts, best_w):
    T, BS = dp.shape
    B = wblk.shape[0]
    pid = pl.program_id(0)
    t0 = tiles[pid] * T
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, BS), 1)

    @pl.when(pid == 0)
    def _():
        def zero(q, _):
            best_ts[q] = jnp.int32(0)
            best_w[q] = jnp.float32(0)
            return 0

        jax.lax.fori_loop(0, B, zero, 0)

    # pid 0 always visits (an identity copy of its tile when the batch
    # touches nothing): the output VMEM windows must be initialized before
    # the pipeline flushes them over the aliased pool buffer
    @pl.when((pid < n_touched[0]) | (pid == 0))
    def _visit():
        # ---- probe pass (pre-append tile contents): one block row at a
        # time as a (1, BS) vector, its newest match by masked reductions
        def probe(q, _):
            sb = pstart[q]
            sz = psize[q]
            v = pv[q]
            nblk = (sz + BS - 1) // BS
            lo = jnp.maximum(sb, t0)
            hi = jnp.minimum(sb + nblk, t0 + T)

            def row(r, _):
                local = r - t0
                d = dp[pl.ds(local, 1), :]
                t = tp[pl.ds(local, 1), :]
                hit = ((r - sb) * BS + lane < sz) & (d == v)
                tm = jnp.max(jnp.where(hit, t, 0))

                @pl.when(tm > best_ts[q])
                def _():
                    best_ts[q] = tm
                    best_w[q] = jnp.sum(jnp.where(hit & (t == tm),
                                                  wp[pl.ds(local, 1), :],
                                                  0.0))

                return 0

            @pl.when((sb >= 0) & (v >= 0))
            def _():
                jax.lax.fori_loop(lo, jnp.maximum(lo, hi), row, 0)

            return 0

        jax.lax.fori_loop(0, B, probe, 0)

        # ---- append pass: copy tile, land this tile's slots (a row
        # read-modify-write: Mosaic stores vectors, not scalars, to VMEM)
        od[...] = dp[...]
        ow[...] = wp[...]
        ot[...] = tp[...]

        def wr(j, _):
            blk = wblk[j]

            @pl.when((wval[j] != 0) & (blk >= t0) & (blk < t0 + T))
            def _():
                b = pl.ds(blk - t0, 1)
                sel = lane == wlane[j]
                od[b, :] = jnp.where(sel, wd[j], od[b, :])
                ow[b, :] = jnp.where(sel, ww[j], ow[b, :])
                ot[b, :] = jnp.where(sel, wts[j], ot[b, :])

            return 0

        jax.lax.fori_loop(0, B, wr, 0)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def append_pallas(dst, w, ts, wblk, wlane, wval, wd, ww, wts,
                  pstart, psize, pv, tiles=None, n_touched=None,
                  tile: int = 128, interpret: bool | None = None):
    """Drop-in for ``ref.append_ref`` (same outputs). ``tiles`` is the
    prefetched visit order — touched pool tiles first (ascending), then the
    last touched tile repeated out to the grid length; ``n_touched`` is its
    valid prefix. Omitting both falls back to visiting every tile."""
    if interpret is None:
        interpret = ops.interpret()
    NB, BS = dst.shape
    tile = append_tile_rows(NB, tile)
    n_tiles = NB // tile
    if tiles is None:
        tiles = jnp.arange(n_tiles, dtype=jnp.int32)
        n_touched = jnp.asarray(n_tiles, jnp.int32)
    ptile = pl.BlockSpec((tile, BS), lambda i, tl, nt: (tl[i], 0))
    # per-op vectors and the probe accumulators are whole-array SMEM
    # residents: the kernel reads and updates them one scalar at a time
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_tiles,),
        in_specs=[ptile, ptile, ptile] + [smem] * 9,
        out_specs=[ptile, ptile, ptile, smem, smem],
    )
    B = wblk.shape[0]
    nd, nw, nt, best_ts, best_w = pl.pallas_call(
        _append_kernel,
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((NB, BS), dst.dtype),
            jax.ShapeDtypeStruct((NB, BS), w.dtype),
            jax.ShapeDtypeStruct((NB, BS), ts.dtype),
            jax.ShapeDtypeStruct((B,), jnp.int32),
            jax.ShapeDtypeStruct((B,), jnp.float32),
        ],
        # pool payloads alias their outputs: untouched tiles keep their
        # contents without a copy (operand indices count the two
        # scalar-prefetch arguments)
        input_output_aliases={2: 0, 3: 1, 4: 2},
        interpret=interpret,
    )(tiles, jnp.reshape(jnp.asarray(n_touched, jnp.int32), (1,)),
      dst, w, ts, wblk, wlane, wval.astype(jnp.int32), wd,
      ww.astype(jnp.float32), wts, pstart, psize, pv)
    return nd, nw, nt, (best_ts > 0) & (best_w != 0)
