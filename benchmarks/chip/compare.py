"""The comparison that decides ``correct``, and its control.

After the window closes, what the timed path produced is compared with
the plain reference (``reference.py``) of the exact op prefix it had been
given:

* the final state, through the store's own reads: ``num_edges``, and
  ``degree`` and ``neighbors`` (IDs and weights) of a seeded sample of
  vertices, against the reference of every op applied;
* a seeded sample of the window's own read answers, each against the
  reference of its epoch's prefix;
* the last analytics answer of the window, against the reference's
  float64 PageRank of its epoch's prefix.

Every number has a limit; a run is correct when no number passes its
limit. The store's answers are exact except PageRank, which sums in
float32 on the device. The limits and the readings they were set from are
in PERF.md.

The control puts the reference itself in the program's place, computed
one precision lower than the configuration states: edge weights rounded
to bfloat16 (float32 stated), PageRank iterated in bfloat16 (float32
stated). It must come out not correct.
"""
from __future__ import annotations

import itertools

import numpy as np

from reference import Reference

# exact answers have the limit 0; PageRank's limit lies between the
# program's largest reading and the control's smallest (PERF.md)
LIMITS = {
    "failed": 0,
    "num_edges_gap": 0,
    "degree_mismatch": 0,
    "neighbor_id_mismatch": 0,
    "weight_gap": 0.0,
    "read_id_mismatch": 0,
    "read_weight_gap": 0.0,
    "pagerank_rel_err": 2e-4,
}


def neighbor_gaps(ids, ref: Reference, xs, answers):
    """(vertices whose neighbor ID multiset differs, the largest weight
    gap among the rest) of ``answers[i] = (neighbor IDs, weights)`` of
    vertex ``xs[i]``."""
    mismatch, gap = 0, 0.0
    for x, ans in itertools.zip_longest(xs, answers):
        if ans is None:                 # no answer for this vertex
            mismatch += 1
            continue
        nid, nw = ans
        d, w = ref.neighbors(int(x))
        want_ids = ids[d]
        o = np.argsort(want_ids, kind="stable")
        got = np.asarray(nid, np.uint64)
        g = np.argsort(got, kind="stable")
        if got.shape != want_ids.shape or \
                not np.array_equal(got[g], want_ids[o]):
            mismatch += 1
            continue
        if len(d):
            gap = max(gap, float(np.max(np.abs(
                np.asarray(nw, np.float64)[g] - w[o].astype(np.float64)))))
    return mismatch, gap


def pagerank_rel_err(ref: Reference, got: np.ndarray, iters: int) -> float:
    """Largest relative error over the registered vertices. ``got`` is NaN
    where the answer names no value: a registered vertex without one reads
    as rank 0 (error 1), an unregistered vertex with one as error 1, and a
    value that is not finite as 1e30."""
    want = ref.pagerank(iters)
    p = ref.present
    if not p.any():
        return 0.0
    err = float(np.max(np.abs(np.nan_to_num(got[p], nan=0.0) - want[p])
                       / want[p]))
    if np.any(~np.isnan(got[~p])):
        err = max(err, 1.0)
    return err if np.isfinite(err) else 1e30


class Run:
    """What the window left for the check: the graph, the op log, the
    recorded read answers (prefix length, vertex indices, answer) and the
    last analytics answer (prefix length, per-vertex array)."""

    def __init__(self, graph, traffic, undirected: bool):
        self.graph = graph
        self.traffic = traffic
        self.undirected = undirected
        self.reads = []
        self.analytics = None
        self.failed = 0
        self._refs = {}

    def ref(self, n: int) -> Reference:
        r = self._refs.get(n)
        if r is None:
            r = self._refs[n] = Reference(self.graph["V"],
                                          *self.traffic.prefix(n),
                                          undirected=self.undirected)
        return r


class ControlAnswers:
    """The reference in the program's place, one precision lower."""

    def __init__(self, run: Run):
        self.run = run

    def _bf16(self, w):
        import ml_dtypes
        return np.asarray(w, np.float32).astype(ml_dtypes.bfloat16) \
            .astype(np.float32)

    def _neighbors(self, ref, xs):
        ids = self.run.graph["ids"]
        return [(ids[d], self._bf16(w))
                for d, w in (ref.neighbors(int(x)) for x in xs)]

    def final(self, ref, xs):
        return ref.num_edges, ref.degree(xs), self._neighbors(ref, xs)

    def read(self, k: int):
        n, xs, _ = self.run.reads[k]
        return self._neighbors(self.run.ref(n), xs)

    def pagerank(self, iters: int) -> np.ndarray:
        import jax.numpy as jnp
        n_pre, _ = self.run.analytics
        ref = self.run.ref(n_pre)
        bf = jnp.bfloat16
        d = 0.85
        src = jnp.asarray(ref.src, jnp.int32)
        dst = jnp.asarray(ref.dst, jnp.int32)
        present = jnp.asarray(ref.present)
        deg = jnp.asarray(np.diff(ref.indptr), bf)
        n = jnp.asarray(float(ref.present.sum()), bf)
        pr = jnp.where(present, 1 / n, 0).astype(bf)
        for _ in range(iters):
            contrib = jnp.where(deg > 0, pr / jnp.maximum(deg, 1), 0).astype(bf)
            dangling = jnp.sum(jnp.where(present & (deg == 0), pr, 0),
                               dtype=bf)
            inflow = jnp.zeros(ref.V, bf).at[dst].add(contrib[src])
            pr = jnp.where(present, (1 - d) / n + d * (inflow + dangling / n),
                           0).astype(bf)
        out = np.asarray(pr.astype(jnp.float32), np.float64)
        out[~ref.present] = np.nan
        return out


def compare(run: Run, answers, sample: np.ndarray, n_reads: int, seed_rng,
            iters: int | None) -> dict:
    """The numbers compared, each as {"value": v, "limit": l}.
    ``answers`` gives the final state's answers, the window's read answers
    and its last analytics answer (the program's, or the control's)."""
    ids = run.graph["ids"]
    out = {"failed": run.failed}
    ref = run.ref(run.traffic.n_logged)
    m, deg, nbrs = answers.final(ref, sample)
    out["num_edges_gap"] = abs(int(m) - ref.num_edges)
    out["degree_mismatch"] = int(np.sum(np.asarray(deg) != ref.degree(sample)))
    out["neighbor_id_mismatch"], out["weight_gap"] = neighbor_gaps(
        ids, ref, sample, nbrs)
    if run.reads:
        picks = np.sort(seed_rng.choice(len(run.reads),
                                        min(n_reads, len(run.reads)),
                                        replace=False))
        mis, gap = 0, 0.0
        for k in picks:
            n, xs, _ = run.reads[k]
            a, b = neighbor_gaps(ids, run.ref(n), xs, answers.read(int(k)))
            mis, gap = mis + a, max(gap, b)
        out["read_id_mismatch"], out["read_weight_gap"] = mis, gap
    if run.analytics is not None:
        out["pagerank_rel_err"] = pagerank_rel_err(
            run.ref(run.analytics[0]), answers.pagerank(iters), iters)
    return {k: {"value": v, "limit": LIMITS[k]} for k, v in out.items()}


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())
