"""Pallas TPU kernel: fused SORT descent (batched vertex-ID lookup).

The lookup is ``l`` dependent gathers. XLA materializes each layer's node-id
vector in HBM between gathers; the fused kernel keeps the whole descent in
registers/VMEM — keys stream in as tiles, node pools stay in HBM/ANY and are
hit with scalar dynamic loads (TPU's scalar core drives the address chase
while the next key tile is prefetched).

Layer structure (fan-outs, bit offsets) is static; the kernel is specialized
per SORT configuration. Validated in interpret mode vs ``ref.sort_lookup_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ops

__all__ = ["sort_lookup_pallas"]


def _make_kernel(layers: int, fanout_bits, bit_offsets, tile: int):
    def kernel(*refs):
        keys_ref = refs[0]
        pool_refs = refs[1:1 + layers]
        out_ref = refs[1 + layers]

        def body(k, _):
            hi = keys_ref[k, 0]
            lo = keys_ref[k, 1]
            node = jnp.int32(0)
            valid = jnp.bool_(True)
            for i in range(layers):
                a, boff = fanout_bits[i], bit_offsets[i]
                mask = jnp.uint32((1 << a) - 1)
                if boff >= 32:
                    idx = (hi >> jnp.uint32(boff - 32)) & mask
                elif boff + a <= 32:
                    idx = (lo >> jnp.uint32(boff)) & mask
                else:
                    lo_bits = 32 - boff
                    idx = (((hi & jnp.uint32((1 << (boff + a - 32)) - 1))
                            << jnp.uint32(lo_bits)) | (lo >> jnp.uint32(boff)))
                slot = node * (1 << a) + idx.astype(jnp.int32)
                child = pool_refs[i][pl.ds(slot, 1)][0]
                child = jnp.where(valid, child, -1)
                valid = child >= 0
                node = jnp.maximum(child, 0)
            out_ref[pl.ds(k, 1)] = jnp.where(valid, node, -1)[None]
            return 0

        jax.lax.fori_loop(0, tile, body, 0)

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("fanout_bits", "bit_offsets", "tile",
                                    "interpret"))
def sort_lookup_pallas(pools, counts, keys, *, fanout_bits, bit_offsets,
                       tile: int = 256, interpret: bool | None = None):
    """(B, 2) uint32 keys -> int32 offsets. B must be a multiple of ``tile``
    (callers pad; the facade's batches are power-of-two sized)."""
    if interpret is None:
        interpret = ops.interpret()
    layers = len(fanout_bits)
    B = keys.shape[0]
    tile = min(tile, B)
    assert B % tile == 0, "pad the key batch to a multiple of the tile"
    grid = (B // tile,)

    in_specs = [pl.BlockSpec((tile, 2), lambda i: (i, 0))]
    # node pools stay unblocked in ANY memory (HBM); scalar loads chase them
    for _ in range(layers):
        in_specs.append(pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY))

    out = pl.pallas_call(
        _make_kernel(layers, tuple(fanout_bits), tuple(bit_offsets), tile),
        grid=grid,
        in_specs=in_specs,
        out_specs=pl.BlockSpec((tile,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((B,), jnp.int32),
        interpret=interpret,
    )(keys, *pools)
    return out
