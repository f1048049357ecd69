"""Shared helpers of the chip benchmark's CPU tests: the harness modules
on the import path, and cells shrunk to a size the CPU runs in seconds."""
import copy
import dataclasses
import pathlib
import sys

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmarks" / "chip"
if str(BENCH) not in sys.path:
    sys.path.insert(0, str(BENCH))


def shrink(cell, scale=10):
    """The cell at 2^scale vertices (64 inserts each) with capacities and
    mix sizes cut to match; the widths of the tiers shrink with them, so
    every code path of the full cell still runs."""
    cell = dataclasses.replace(cell, config=copy.deepcopy(cell.config),
                               mix=copy.deepcopy(cell.mix))
    cfg, mix = cell.config, cell.mix
    cfg["vertices"] = 1 << scale
    cfg["edges"] = 64 << scale
    cfg["capacity"].update(batch=512, query_batch=64, k_max=64, dmax=256,
                           probe_width=64)
    mix["preload_ops"] = min(mix["preload_ops"], 2048)
    mix["write_ops_per_chip"] = max(64, mix["write_ops_per_chip"] // 16)
    mix["check"] = dict(mix["check"], vertices=256)
    return cell


@pytest.fixture
def tiny():
    import harness

    def make(name, scale=10):
        return shrink(harness.resolve_cell(name), scale)
    return make
