"""Jit'd public wrappers around the Pallas kernels, with jnp references.

Which implementation runs is decided here and nowhere else, at call (trace)
time — importing this module never touches a backend:

* ``impl='auto'`` takes the kernel's entry in ``TPU_IMPL`` when the backend
  is a TPU, and the jnp reference (``kernels/ref.py``) everywhere else;
* an explicit ``impl='pallas'`` or ``impl='ref'`` always wins;
* a Pallas kernel runs compiled on a TPU and in interpret mode off it.

``TPU_IMPL`` is a static table, never a runtime fallback: a kernel whose
entry is ``'ref'`` has a documented reason (CHANGES.md) for staying off the
TPU default.
"""
from __future__ import annotations

import jax

from . import ref as _ref

# Static per-kernel choice on a TPU backend.
#  append      — compiles, but its grid visits every touched pool tile and
#                loops over all B probes and B writes in each, so a uniform
#                batch on a deployment-size pool moves nearly the whole pool
#                through VMEM per batch; the jnp scatter + windowed probe
#                stays the default until the kernel is redesigned.
#  sort_lookup — Mosaic refuses it: "Loads are only allowed on VMEM and SMEM
#                references" (it loads from ANY/HBM refs); off the store path.
#  frontier    — Mosaic refuses it: "Cannot store scalars to VMEM"; off the
#                store path.
TPU_IMPL = {
    "append": "ref",
    "compact_rows": "pallas",
    "defrag_rows": "pallas",
    "sort_lookup": "ref",
    "frontier": "ref",
}


def platform() -> str:
    """The backend the next trace runs on (queried at call time)."""
    return jax.default_backend()


def resolve(kernel: str, impl: str = "auto") -> str:
    """'pallas' or 'ref' for ``kernel`` under the ``impl`` knob."""
    if impl == "auto":
        return TPU_IMPL[kernel] if platform() == "tpu" else "ref"
    if impl not in ("pallas", "ref"):
        raise ValueError(f"unknown kernel impl {impl!r}")
    return impl


def interpret() -> bool:
    """Pallas interpret mode: exactly when the backend is not a TPU."""
    return platform() != "tpu"


def append_tile_rows(nb: int, tile: int = 128) -> int:
    """Pool block rows per append-kernel grid step (see kernels/append.py)
    — exposed so callers computing the touched-tile prefetch list agree
    with the kernel's tiling."""
    from .append import append_tile_rows as _atr
    return _atr(nb, tile)


def append_edges(dst, w, ts, wblk, wlane, wval, wd, ww, wts,
                 pstart, psize, pv, tiles=None, n_touched=None,
                 impl: str = "auto"):
    """Fused edge append: slot scatter of (dst, weight, ts) + pre-append
    last-writer pair-liveness probe, bounded to the prefetched ``tiles``
    list (touched pool tiles; the ref oracle is dense and ignores it).
    See ref.append_ref."""
    if resolve("append", impl) == "pallas":
        from .append import append_pallas
        return append_pallas(dst, w, ts, wblk, wlane, wval, wd, ww, wts,
                             pstart, psize, pv, tiles, n_touched,
                             interpret=interpret())
    return _ref.append_ref(dst, w, ts, wblk, wlane, wval, wd, ww, wts,
                           pstart, psize, pv)


def compact_rows(dst, w, ts, size, read_ts=None, impl: str = "auto"):
    """Batched log compaction (paper Alg. 2). See ref.compact_rows_ref."""
    if resolve("compact_rows", impl) == "pallas":
        from .compact import compact_rows_pallas
        return compact_rows_pallas(dst, w, ts, size, read_ts=read_ts,
                                   interpret=interpret())
    return _ref.compact_rows_ref(dst, w, ts, size, read_ts=read_ts)


def defrag_rows(dst, w, ts, size, keep_all: bool = False,
                impl: str = "auto"):
    """Defrag row compactor: last-writer dedup + tombstone drop with
    destination-ASCENDING emission (the streaming rebuild's per-vertex
    pass). ``keep_all`` (the 'grow' policy) always runs the jnp oracle — it
    keeps every version, which the kernel does not express.
    See ref.defrag_rows_ref; returns (dst', w', ts', count, live)."""
    if resolve("defrag_rows", impl) == "pallas" and not keep_all:
        from .compact import defrag_rows_pallas
        return defrag_rows_pallas(dst, w, ts, size, interpret=interpret())
    return _ref.defrag_rows_ref(dst, w, ts, size, keep_all=keep_all)


def sort_lookup(pools, counts, keys, *, fanout_bits, bit_offsets,
                impl: str = "auto"):
    if resolve("sort_lookup", impl) == "pallas":
        from .sort_lookup import sort_lookup_pallas
        return sort_lookup_pallas(pools, counts, keys, fanout_bits=fanout_bits,
                                  bit_offsets=bit_offsets,
                                  interpret=interpret())
    return _ref.sort_lookup_ref(pools, counts, keys, fanout_bits=fanout_bits,
                                bit_offsets=bit_offsets)


def frontier_expand(owner, dst, valid, frontier_bits, visited_bits,
                    impl: str = "auto"):
    if resolve("frontier", impl) == "pallas":
        from .frontier import frontier_pallas
        return frontier_pallas(owner, dst, valid, frontier_bits, visited_bits,
                               interpret=interpret())
    return _ref.frontier_ref(owner, dst, valid, frontier_bits, visited_bits)
