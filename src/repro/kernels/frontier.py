"""Pallas TPU kernel: BFS frontier expansion over the flat edge pool.

This is the edge-chain payoff (paper §3.3 Fig. 6): traversal chases vertex
*offsets* straight out of edge blocks — no vertex-index lookups. One grid
step processes a tile of pool blocks; the frontier bitmap and the
accumulating next-frontier bitmap both live in VMEM (the same segmented
bitmap the duplicate checker uses).

The output bitmap block maps to the *same* window every grid step — TPU
grids are sequential, so read-modify-write accumulation across steps is
legal (revisiting). Validated in interpret mode vs ``ref.frontier_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ops

__all__ = ["frontier_pallas"]


def _kernel(owner_ref, dst_ref, valid_ref, fbits_ref, out_ref):
    TB, BS = dst_ref.shape

    @pl.when(pl.program_id(0) == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    def per_block(b, _):
        o = owner_ref[b]
        fw = fbits_ref[jnp.right_shift(jnp.maximum(o, 0), 5)]
        on = (o >= 0) & (((fw >> (jnp.maximum(o, 0) & 31).astype(jnp.uint32))
                          & 1) == 1)

        def per_lane(j, _):
            d = dst_ref[b, j]
            ok = on & valid_ref[b, j] & (d >= 0)

            @pl.when(ok)
            def _():
                w = jnp.right_shift(d, 5)
                bit = jnp.uint32(1) << (d & 31).astype(jnp.uint32)
                out_ref[w] = out_ref[w] | bit

            return 0

        jax.lax.fori_loop(0, BS, per_lane, 0)
        return 0

    jax.lax.fori_loop(0, TB, per_block, 0)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def frontier_pallas(owner, dst, valid, frontier_bits, visited_bits,
                    tile: int = 128, interpret: bool | None = None):
    if interpret is None:
        interpret = ops.interpret()
    NB, BS = dst.shape
    tile = min(tile, NB)
    assert NB % tile == 0, "pad the pool to a multiple of the block tile"
    W = frontier_bits.shape[0]
    grid = (NB // tile,)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile,), lambda i: (i,)),
            pl.BlockSpec((tile, BS), lambda i: (i, 0)),
            pl.BlockSpec((tile, BS), lambda i: (i, 0)),
            pl.BlockSpec((W,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((W,), lambda i: (0,)),  # revisited every step
        out_shape=jax.ShapeDtypeStruct((W,), jnp.uint32),
        interpret=interpret,
    )(owner, dst, valid, frontier_bits)
    return out & ~visited_bits
