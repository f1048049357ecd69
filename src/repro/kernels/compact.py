"""Pallas TPU kernels: log compaction (paper Algorithm 2) and the defrag
row compactor.

One grid step owns a block of ``_ROWS`` edge arrays (the sublane tile) at
full row width D, resident in VMEM. The paper's reverse scan with a
duplicate-checker bitmap becomes a reverse scan over positions that works
on all rows of the block at once:

* position j's (dst, weight, ts) are read as one (rows, 1) column by a
  masked lane reduction — Mosaic has no scalar loads from VMEM, and a
  vector select-and-reduce is its idiom for a dynamic column;
* the duplicate checker is a (rows, D) ``seen`` mask instead of a bitmap
  over the vertex universe: when position j is scanned, every earlier
  position holding the same destination is marked in one vector compare,
  so its cost is O(D / 128) vector ops per scanned position and it needs
  no VMEM proportional to the vertex count;
* survivors land at their emission rank with a vector select over the
  row (no scalar stores to VMEM).

The scan runs only up to the block's largest occupied size, so empty and
short rows cost little. Validated in interpret mode against
``ref.compact_rows_ref`` / ``ref.defrag_rows_ref``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import ops

__all__ = ["compact_rows_pallas", "defrag_rows_pallas"]

_ROWS = 8   # edge arrays per grid step: one sublane tile


def _col(ref, j):
    """Column ``j`` of a (rows, D) VMEM block as a (rows, 1) vector. Rows
    wider than one lane tile read only the 128-lane chunk holding j."""
    D = ref.shape[1]
    if D > 128 and D % 128 == 0:
        base = pl.multiple_of((j // 128) * 128, 128)
        x = ref[:, pl.ds(base, 128)]
        j = j - base
    else:
        x = ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    return jnp.sum(jnp.where(lane == j, x, jnp.zeros_like(x)), axis=1,
                   keepdims=True)


def _dedup_scan(size_ref, dst_ref, w_ref, ts_ref, seen, on_survivor):
    """Reverse scan shared by both kernels: for every position j (newest
    first) decide whether it is its destination's newest occupied entry
    with a non-NULL weight, call ``on_survivor(j, keep, d, w, t, cnt)``,
    and mark older entries of the same destination as seen. Returns the
    per-row survivor count (rows, 1)."""
    R, D = dst_ref.shape
    size = size_ref[...]
    seen[...] = jnp.zeros_like(seen)
    n = jnp.minimum(jnp.max(size), D)

    def step(i, cnt):
        j = n - 1 - i
        d = _col(dst_ref, j)
        w = _col(w_ref, j)
        t = _col(ts_ref, j)
        valid = (j < size) & (d >= 0)
        keep = valid & (_col(seen, j) == 0) & (w != 0)
        on_survivor(j, keep, d, w, t, cnt)
        seen[...] = jnp.where(valid & (dst_ref[...] == d), 1, seen[...])
        return cnt + keep.astype(jnp.int32)

    return jax.lax.fori_loop(0, n, step, jnp.zeros((R, 1), jnp.int32))


def _place(odst, ow, ots, at, d, w, t):
    """Write (d, w, t) into lane ``at`` of each row where ``at`` matches."""
    lane = jax.lax.broadcasted_iota(jnp.int32, odst.shape, 1)
    put = lane == at
    odst[...] = jnp.where(put, d, odst[...])
    ow[...] = jnp.where(put, w, ow[...])
    ots[...] = jnp.where(put, t, ots[...])


def _clear(odst, ow, ots):
    odst[...] = jnp.full(odst.shape, -1, odst.dtype)
    ow[...] = jnp.zeros(ow.shape, ow.dtype)
    ots[...] = jnp.zeros(ots.shape, ots.dtype)


def _compact_kernel(size_ref, dst_ref, w_ref, ts_ref, odst, ow, ots, ocnt,
                    seen):
    """Survivors in reverse-scan order (newest first), front-packed."""
    _clear(odst, ow, ots)

    def emit(j, keep, d, w, t, cnt):
        _place(odst, ow, ots, jnp.where(keep, cnt, -1), d, w, t)

    ocnt[...] = _dedup_scan(size_ref, dst_ref, w_ref, ts_ref, seen, emit)


def _defrag_kernel(size_ref, dst_ref, w_ref, ts_ref, odst, ow, ots, ocnt,
                   seen, live):
    """Survivors sorted by destination ASCENDING (the defrag's CSR
    discipline): the reverse scan marks each row's survivors in ``live``,
    then a second pass places survivor j at its destination's rank among
    the row's live destinations."""
    R, D = dst_ref.shape
    _clear(odst, ow, ots)
    live[...] = jnp.zeros_like(live)
    lane = jax.lax.broadcasted_iota(jnp.int32, (R, D), 1)

    def mark(j, keep, d, w, t, cnt):
        live[...] = jnp.where(lane == j, keep.astype(jnp.int32), live[...])

    cnt = _dedup_scan(size_ref, dst_ref, w_ref, ts_ref, seen, mark)
    ocnt[...] = cnt
    n = jnp.minimum(jnp.max(size_ref[...]), D)

    def place(j, _):
        d = _col(dst_ref, j)
        keep = _col(live, j) != 0
        rank = jnp.sum(jnp.where((live[...] != 0) & (dst_ref[...] < d), 1, 0),
                       axis=1, keepdims=True)
        _place(odst, ow, ots, jnp.where(keep, rank, -1), d,
               _col(w_ref, j), _col(ts_ref, j))
        return 0

    jax.lax.fori_loop(0, n, place, 0)


def _rows_call(kernel, n_scratch, dst, w, ts, size, interpret):
    """Run a row kernel over (K, D) edge arrays in blocks of ``_ROWS``
    rows (K padded with empty rows; weights ride as float32)."""
    K, D = dst.shape
    Kp = -(-K // _ROWS) * _ROWS
    pad = ((0, Kp - K), (0, 0))
    wdtype = w.dtype
    args = (jnp.pad(size.astype(jnp.int32), (0, Kp - K)).reshape(Kp, 1),
            jnp.pad(dst, pad, constant_values=-1),
            jnp.pad(w.astype(jnp.float32), pad),
            jnp.pad(ts, pad))
    row = pl.BlockSpec((_ROWS, D), lambda i: (i, 0))
    col = pl.BlockSpec((_ROWS, 1), lambda i: (i, 0))
    odst, ow, ots, ocnt = pl.pallas_call(
        kernel,
        grid=(Kp // _ROWS,),
        in_specs=[col, row, row, row],
        out_specs=[row, row, row, col],
        out_shape=[
            jax.ShapeDtypeStruct((Kp, D), dst.dtype),
            jax.ShapeDtypeStruct((Kp, D), jnp.float32),
            jax.ShapeDtypeStruct((Kp, D), ts.dtype),
            jax.ShapeDtypeStruct((Kp, 1), jnp.int32),
        ],
        scratch_shapes=[pltpu.VMEM((_ROWS, D), jnp.int32)] * n_scratch,
        interpret=interpret,
    )(*args)
    return odst[:K], ow[:K].astype(wdtype), ots[:K], ocnt[:K, 0]


@functools.partial(jax.jit, static_argnames=("interpret",))
def compact_rows_pallas(dst, w, ts, size, read_ts=None, *,
                        interpret: bool | None = None):
    """Drop-in for ``ref.compact_rows_ref`` (same outputs, same order)."""
    if interpret is None:
        interpret = ops.interpret()
    if read_ts is not None:  # MVCC filter applied before the scan
        dst = jnp.where(ts <= jnp.asarray(read_ts, ts.dtype), dst, -1)
    return _rows_call(_compact_kernel, 1, dst, w, ts, size, interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def defrag_rows_pallas(dst, w, ts, size, *, interpret: bool | None = None):
    """Drop-in for ``ref.defrag_rows_ref`` (dedup mode only — the 'grow'
    policy's keep-everything variant stays on the jnp oracle). Returns
    (dst', w', ts', count, live) with live == count: dedup mode keeps
    exactly the live pairs."""
    if interpret is None:
        interpret = ops.interpret()
    odst, ow, ots, cnt = _rows_call(_defrag_kernel, 2, dst, w, ts, size,
                                    interpret)
    return odst, ow, ots, cnt, cnt
