"""Peaks of each chip, and the bytes a kernel's work must move, computed
from static sizes so that the same work is counted whatever implements it.

``peaks.json`` is keyed by ``device_kind`` as JAX reports it; a chip that
is not in it is an error, never a default.
"""
from __future__ import annotations

import json
import pathlib

PEAKS_FILE = pathlib.Path(__file__).resolve().parent / "peaks.json"
LANES = 3          # an edge entry is (dst int32, weight float32, ts int32)
WORD = 4


def peaks(device_kind: str) -> dict:
    table = json.loads(PEAKS_FILE.read_text())
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS_FILE.name}; known: {sorted(table)}")
    return table[device_kind]


def compact_rows_bytes(rows: int, width: int) -> int:
    """One compaction of ``rows`` edge arrays of ``width`` entries: read
    each entry's three lanes and the row's size, write the compacted
    three lanes and the row's count."""
    return 2 * rows * (width * LANES * WORD + WORD)


def step_compact_bytes(store_kwargs: dict) -> int:
    """Per edge-update batch, the step's two compaction tiers: up to
    ``k_max`` vertices at the probe window's width, and up to ``k_big`` at
    the full ``dmax`` (``core/edgepool.py`` ``apply_edge_updates``)."""
    dmax = store_kwargs["dmax"]
    narrow = min(store_kwargs["probe_width"], dmax)
    b = compact_rows_bytes(store_kwargs["k_max"], narrow)
    if narrow < dmax:
        b += compact_rows_bytes(store_kwargs["k_big"], dmax)
    return b


KERNEL = r"^%compact_rows_pallas\.\d+ = "   # the Pallas call's op name


def compact_rows_roofline(ctx, module: str, bytes_per_execution: int):
    """Share (%) of its roofline that ``compact_rows`` reaches inside the
    program ``module``: the least time its bytes take at the chip's HBM
    bandwidth, over the device time of its ops (``KERNEL``) there. None
    when the trace holds no such op."""
    t = ctx.trace
    if t is None:
        return None
    secs = t.op_seconds(KERNEL, module=module)
    n = t.module_count(module)
    if secs <= 0 or not n:
        return None
    return 100.0 * n * bytes_per_execution / ctx.peaks["hbm_bytes_per_s"] \
        / secs
