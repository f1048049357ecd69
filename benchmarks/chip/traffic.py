"""The one traffic generator. A mix is a data file (``traffic/<mix>.json``)
of parameters that this module reads; a new mix is a new file.

A run is a sequence of rounds after an optional preload, driven by the
mix's ``loop`` (``loops/closed.py``: back to back). Each round is one
write (``write_ops_per_chip`` x chips undirected edge ops, split by ``mix``
into further inserts of the seeded stream, weight updates and deletes of
loaded edges, in a seeded order), then optionally ``capture`` (seal an
epoch), one ``read`` and, every ``analytics.every``-th round, one
analytics query on that round's epoch. Sizes are fixed by the mix; the
seed only changes which vertices and edges they touch, so every seed
offers the same amount of work.

Parameters (all keys present; ``read`` and ``analytics`` may be null):

    loop                how rounds are driven: ``loops/<loop>.py``
    preload_ops         inserts applied in set-up before the first round
    warmup_rounds       rounds run in set-up (every program the window
                        uses runs in them; round 0 always runs analytics)
    write_ops_per_chip  undirected edge ops per write, per chip
    mix                 {"insert": f, "update": f, "delete": f}, sum 1
    update_weights      [lo, hi] of an update's new weight
    capture             seal an epoch after each write
    read                {"kind": "neighbors", "ids": n, "zipf": s}: n IDs
                        drawn Zipf(s) over the loaded vertices, hottest
                        rank at a seeded vertex (YCSB's scrambled zipfian)
    analytics           {"name": ..., "params": {...}, "every": N}
    check               {"vertices": n, "reads": k}: the final check's
                        sample of vertices, and how many window reads it
                        compares
"""
from __future__ import annotations

import json
import pathlib

import numpy as np

from ustream import rng_for

KEYS = ("loop", "preload_ops", "warmup_rounds", "write_ops_per_chip", "mix",
        "update_weights", "capture", "read", "analytics", "check")


def load_mix(path: pathlib.Path) -> dict:
    mix = json.loads(path.read_text())
    missing = [k for k in KEYS if k not in mix]
    if missing:
        raise ValueError(f"{path}: traffic keys missing: {missing}")
    if abs(sum(mix["mix"].values()) - 1.0) > 1e-9:
        raise ValueError(f"{path}: mix fractions must sum to 1")
    return mix


class Traffic:
    """Rounds of ops, as vertex indices of the configuration's graph. Every
    op handed out is appended to the op log, so ``prefix(n)`` is exactly
    the first ``n`` ops the store was given."""

    def __init__(self, mix: dict, graph: dict, chips: int, seed: int):
        self.mix = mix
        self.g = graph
        self.write_ops = mix["write_ops_per_chip"] * chips
        n = self.write_ops
        f = mix["mix"]
        self.n_upd = int(round(f.get("update", 0.0) * n))
        self.n_del = int(round(f.get("delete", 0.0) * n))
        self.n_ins = n - self.n_upd - self.n_del
        self.cursor = 0                       # next insert of the stream
        self.loaded = 0                       # inserts loaded by preload
        self.log_u, self.log_v, self.log_w = [], [], []
        self.n_logged = 0
        self._wrng = rng_for(seed, 1)
        self._rrng = rng_for(seed, 2)
        self._zipf = None

    # ---- writes ----
    def _log(self, u, v, w):
        self.log_u.append(u)
        self.log_v.append(v)
        self.log_w.append(w)
        self.n_logged += len(u)
        return u, v, w

    def _inserts(self, n: int):
        lo, hi = self.cursor, self.cursor + n
        if hi > self.g["E"]:
            raise RuntimeError(f"the insert stream ran out at op {lo} "
                               f"({self.g['E']} in the configuration)")
        self.cursor = hi
        g = self.g
        return g["su"][lo:hi], g["sv"][lo:hi], g["w"][lo:hi]

    def preload(self):
        n = self.mix["preload_ops"]
        out = self._log(*self._inserts(n))
        self.loaded = self.cursor
        return out

    def write(self):
        """The next round's write: inserts continue the stream; updates and
        deletes pick among the loaded edges (the preload's, or every insert
        so far when nothing was preloaded)."""
        u, v, w = self._inserts(self.n_ins)
        if self.n_upd or self.n_del:
            rng = self._wrng
            pool = self.loaded or self.cursor
            pick = rng.integers(0, pool, self.n_upd + self.n_del)
            lo, hi = self.mix["update_weights"]
            tw = np.concatenate([
                rng.uniform(lo, hi, self.n_upd).astype(np.float32),
                np.zeros(self.n_del, np.float32)])
            u = np.concatenate([u, self.g["su"][pick]])
            v = np.concatenate([v, self.g["sv"][pick]])
            w = np.concatenate([w, tw])
            perm = rng.permutation(len(u))
            u, v, w = u[perm], v[perm], w[perm]
        return self._log(u, v, w)

    def prefix(self, n: int):
        """The first ``n`` logged ops as (u, v, w) arrays."""
        u = np.concatenate(self.log_u)[:n] if self.log_u else np.zeros(0, int)
        v = np.concatenate(self.log_v)[:n] if self.log_v else np.zeros(0, int)
        w = (np.concatenate(self.log_w)[:n] if self.log_w
             else np.zeros(0, np.float32))
        return u, v, w

    # ---- reads ----
    def read_ids(self) -> np.ndarray:
        """Vertex indices of one read: Zipf over the loaded vertices."""
        spec = self.mix["read"]
        if self._zipf is None:
            n = self.loaded or self.cursor
            verts = (np.unique(np.concatenate([self.g["su"][:n],
                                               self.g["sv"][:n]]))
                     if n else np.arange(self.g["V"]))
            verts = self._rrng.permutation(verts)
            p = 1.0 / np.arange(1, len(verts) + 1) ** spec["zipf"]
            self._zipf = (verts, np.cumsum(p) / p.sum())
        verts, cdf = self._zipf
        r = np.searchsorted(cdf, self._rrng.random(spec["ids"]), side="right")
        return verts[np.minimum(r, len(verts) - 1)]

    def analytics_due(self, round_index: int) -> bool:
        a = self.mix["analytics"]
        return a is not None and round_index % a["every"] == 0
