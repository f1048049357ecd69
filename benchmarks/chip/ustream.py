"""The seeded graph of a configuration and the sizes of the store it is
loaded into.

Copied from ``chip_smoke.py`` (``make_stream``, ``store_kwargs``) so that
a change to the smoke cannot move the benchmark's yardstick, and split by
name: the graph comes from ``generators/<generator>.py``, the store and
its capacity rule from ``backends/<backend>.py`` (``named.py``).

A configuration file (``configs/<name>.json``) fixes the generator, the
vertex count and the undirected edge inserts (``vertices``, ``edges``),
the ID universe, the weight range, directedness, the backend and chip
count, and the capacity rule's constants.
"""
from __future__ import annotations

import numpy as np

import named


def rng_for(seed: int, purpose: int) -> np.random.Generator:
    """An independent generator per (seed, purpose). Seeds are any whole
    number, so they enter the SeedSequence modulo 2^64."""
    return np.random.default_rng([purpose, int(seed) % (1 << 64)])


def make_graph(cfg: dict, seed: int, gen=None) -> dict:
    """Vertex IDs and the insert stream: ``ids[i]`` is vertex i's ID;
    insert k is the undirected edge (``su[k]``, ``sv[k]``) with weight
    ``w[k]`` (vertex indices). ``gen`` is the generator's module, by
    default the one the configuration names."""
    V, E = cfg["vertices"], cfg["edges"]
    gen = gen or named.load("generators", cfg["generator"])
    return dict(gen.generate(cfg, V, E, rng_for(seed, 0)), V=V, E=E)


def pow2_at_least(x: int) -> int:
    return 1 << max(0, int(x - 1).bit_length())


def capacity(cfg: dict, n_shards: int) -> dict:
    """The smoke's capacity rule, per shard: every live directed entry
    needs a pool slot, the snapshot-log extents keep up to 2x that after
    a rebuild, and growth between rebuilds takes the rest of a 4x pool;
    the vertex table keeps ``vertex_headroom`` over the vertex count."""
    cap = cfg["capacity"]
    V, E = cfg["vertices"], cfg["edges"]
    per_entry = 2 if cfg["undirected"] else 1
    entries = per_entry * E // n_shards          # directed entries per shard
    bs = cap["block_size"]
    common = dict(key_bits=cfg["id_bits"], block_size=bs,
                  pool_blocks=pow2_at_least(cap["pool_factor"] * entries
                                            // bs),
                  k_max=cap["k_max"], dmax=cap["dmax"],
                  undirected=cfg["undirected"])
    return dict(common=common, V=V, E=E, per_entry=per_entry,
                entries=entries, n_rows=V + int(V * cap["vertex_headroom"]))


def store_kwargs(cfg: dict) -> dict:
    """``make_store`` arguments of the configuration's backend."""
    return named.load("backends", cfg["backend"]).store_kwargs(cfg)
