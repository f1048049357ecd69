"""The harness driven end to end on the CPU at a tiny size, past its look
for a chip: sound runs come out correct; the control (the reference one
precision lower in the program's place) and each fault planted under the
timed path come out not correct."""
import time

import numpy as np
import pytest

import compare
import harness
from reference import Reference
from repro.api import AnalyticsOp, OpBatch, ReadOp, make_store
from repro.api import store as api_store

SEED = 2**31 + 77      # more than 32 signed bits hold


def _run(cell, seconds=1.0, control=False):
    return harness.run_cell(cell, SEED, seconds, False, time.perf_counter(),
                            {"hbm_bytes_per_s": 819e9},
                            with_control=control, log=lambda *a: None)


def test_reference_matches_a_tiny_local_store():
    """The copied reference against LocalStore on a mixed stream with
    updates, deletes and re-inserts: exact edges, PageRank in float32."""
    rng = np.random.default_rng(0)
    V, n = 64, 3000
    ids = rng.choice(1 << 32, V, replace=False).astype(np.uint64)
    u, v = rng.integers(0, V, n), rng.integers(0, V, n)
    w = rng.uniform(0.5, 2.0, n).astype(np.float32)
    w[rng.random(n) < 0.15] = 0.0
    store = make_store("local", n_max=96, expected_n=V, batch=256,
                       pool_blocks=1024, k_max=64, dmax=256, undirected=True)
    for lo in range(0, n, 700):
        store.apply(OpBatch.edges(ids[u[lo:lo + 700]], ids[v[lo:lo + 700]],
                                  w[lo:lo + 700]))
    ref = Reference(V, u, v, w)
    assert store.read(ReadOp("num_edges")) == ref.num_edges
    xs = np.arange(V)
    assert np.array_equal(store.read(ReadOp("degree", ids=ids)),
                          ref.degree(xs))
    nb = store.read(ReadOp("neighbors", ids=ids))
    assert compare.neighbor_gaps(ids, ref, xs, nb) == (0, 0.0)
    pr = store.analytics(AnalyticsOp("pagerank", {"iters": 10}))
    got = np.full(V, np.nan)
    for x in range(V):
        got[x] = pr.get(int(ids[x]), np.nan)
    assert compare.pagerank_rel_err(ref, got, 10) < 1e-5


@pytest.mark.parametrize("name", ["u19.insert", "u19.serve"])
def test_sound_run_is_correct_and_control_is_not(tiny, name):
    out = _run(tiny(name), seconds=2.0, control=True)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert not out["control_correct"]
    assert list(out)[-1] == "checks"
    ctrl = out["control_checks"]
    assert ctrl["weight_gap"]["value"] > 0
    if name == "u19.serve":
        assert out["window"]["reads"] >= 1 and out["window"]["analytics"] >= 1
        assert ctrl["pagerank_rel_err"]["value"] > \
            10 * out["checks"]["pagerank_rel_err"]["value"]


def _unchanged(self, batch):
    """A step that returns its state unchanged (in a step's time, so the
    window does not run through the whole stream)."""
    time.sleep(0.02)
    return api_store.ApplyResult(len(batch), 0)


def _half(orig):
    def apply(self, batch):
        """Half of the batch left out."""
        h = len(batch) // 2
        orig(self, OpBatch.edges(batch.src[:h], batch.dst[:h],
                                 batch.weight[:h]))
        return api_store.ApplyResult(len(batch), 0)
    return apply


def _altered_read(orig):
    def read(self, op, at=None):
        """A neighbors answer altered where it is produced."""
        out = orig(self, op, at)
        if op.kind == "neighbors":
            out = [(i, w * np.float32(1.001)) for i, w in out]
        return out
    return read


def _altered_rank(orig):
    def analytics(self, op, at=None):
        """A PageRank answer altered where it is produced."""
        out = dict(orig(self, op, at))
        k = next(iter(out))
        out[k] *= 1.01
        return out
    return analytics


@pytest.mark.parametrize("fault", ["unchanged", "half", "read", "rank"])
def test_planted_fault_is_not_correct(tiny, monkeypatch, fault):
    L = api_store.LocalStore
    if fault == "unchanged":
        cell, patch = tiny("u19.insert"), ("apply", _unchanged)
    elif fault == "half":
        cell, patch = tiny("u19.insert"), ("apply", _half(L.apply))
    elif fault == "read":
        cell, patch = tiny("u19.insert"), ("read", _altered_read(L.read))
    else:
        cell, patch = tiny("u19.serve"), ("analytics",
                                          _altered_rank(L.analytics))
    monkeypatch.setattr(L, *patch)
    out = _run(cell, seconds=0.5)
    assert not out["correct"], out["checks"]
