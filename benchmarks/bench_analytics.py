"""Incremental epoch-delta analytics harness (``BENCH_analytics.json``).

Per-epoch analytics latency, from-scratch vs warm-started over the epoch
delta, through the unified ``repro.api.GraphStore`` front door on

* the 1-shard ``LocalStore`` (host advances over ``HostCsr`` views), and
* the 4-shard ``ShardedStore`` (subprocess with placeholder devices:
  warm mesh programs seeded from the previous epoch's per-shard values),

under a mixed ingest stream: a powerlaw base load, then chains of delta
epochs sized at ~0.1% / 1% / 10% of the live edge count.  Each timed
epoch runs every registered incremental algorithm BOTH ways on the same
captured handle — the harness asserts the answers agree (exactly, or
under 1e-5 for the tolerance-mode PageRank), so the artifact is a parity
check as well as a latency record.

Delta weights decrease strictly across epochs (disjoint per-epoch
ranges), so updates never increase a weight and the SSSP advance stays
on its monotone fast path; one extra tombstone epoch at the end forces
the guarded algorithms (BFS/WCC/SSSP) through their recorded fallbacks.
Every stream (base and deltas) is applied SYMMETRICALLY — the paper
treats graphs as undirected, and the WCC propagation documents that
assumption (on a one-way edge set its directional fixed point is not
the component labeling, so neither backend would agree with the
union-find advance).

Timing model: the epoch's CSR snapshot (device scan + host pull) is
built once per epoch and needed by BOTH paths — scratch algorithms
consume the device arrays, advances the host view — so it is timed
separately as ``snapshot_ms`` and charged to neither.  The delta diff
(``delta_extract_ms``) is pure incremental infrastructure paid once per
epoch and shared by every chained algorithm, so each op's
``incremental_ms`` charges an equal 1/n_ops share of it on top of its
advance; ``scratch_ms`` is the algorithm alone on the same pre-built
snapshot.

    PYTHONPATH=src python -m benchmarks.bench_analytics            # full
    PYTHONPATH=src python -m benchmarks.bench_analytics --smoke    # CI
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import subprocess
import sys
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]
OUT = ROOT / "BENCH_analytics.json"

FULL = dict(n_vertices=8192, base_ops=65536, epochs=5)
SMOKE = dict(n_vertices=512, base_ops=4096, epochs=2)
FRACS = (0.001, 0.01, 0.1)      # delta size as a fraction of live edges


def _ops(src_id):
    """Every registered algorithm with an incremental phase. PageRank
    runs in tolerance mode (``tol`` set): the fixed-iteration default is
    path-dependent and deliberately refuses to advance."""
    from repro.api import AnalyticsOp
    return [
        AnalyticsOp("pagerank", dict(iters=200, damping=0.85, tol=1e-7)),
        AnalyticsOp("wcc", dict(max_iters=64)),
        AnalyticsOp("bfs", dict(source=src_id, max_iters=32)),
        AnalyticsOp("sssp", dict(source=src_id, max_iters=64)),
        AnalyticsOp("degree_map", {}),
        AnalyticsOp("num_edges", {}),
    ]


def _max_err(a, b) -> float:
    """Max abs difference between two normalized analytics answers."""
    if isinstance(a, dict):
        if set(a) != set(b):
            return float("inf")
        if not a:
            return 0.0
        ks = sorted(a)
        va = np.array([float(a[k]) for k in ks], np.float64)
        vb = np.array([float(b[k]) for k in ks], np.float64)
        return float(np.abs(va - vb).max())
    return abs(float(a) - float(b))


def _sym(s, d, w):
    """Symmetrize a stream: every op applied in both directions."""
    return (np.concatenate([s, d]), np.concatenate([d, s]),
            np.concatenate([w, w]))


def _delta_batch(rng, ids, n: int, k: int):
    """One delta epoch's ops (``n`` directed writes, applied as ``n/2``
    symmetric pairs): endpoints from the seen ID pool, weights in the
    epoch-k band ``[0.5, 0.9] * 0.5**k`` — strictly below every earlier
    band (base weights are >= 1.0), so an update is always a decrease
    and the monotone advances never have to refuse."""
    from repro.api import OpBatch
    lo, hi = 0.5 * 0.5 ** k, 0.9 * 0.5 ** k
    half = max(2, n // 2)
    s = ids[rng.integers(0, len(ids), half)]
    d = ids[rng.integers(0, len(ids), half)]
    w = rng.uniform(lo, hi, half).astype(np.float32)
    return OpBatch.edges(*_sym(s, d, w))


def run_chain(store, ids: np.ndarray, epochs: int, seed: int = 0):
    """Drive one store through the delta-epoch chains, timing every
    algorithm scratch vs incremental per epoch.  Returns the result
    dict for the backend section of ``BENCH_analytics.json``."""
    from repro.api import OpBatch, ReadOp

    rng = np.random.default_rng(seed + 17)
    ops = _ops(int(ids[0]))
    m_live = store.read(ReadOp("num_edges"))
    build_csr = store._csrs if hasattr(store, "_csrs") else store._csr

    # base-epoch warmup: compiles every scratch program, seeds the chain
    ep = store.capture()
    warm = {o.name: store.analytics_result(o, ep) for o in ops}
    # one untimed warmup epoch compiles the snapshot/delta pull and every
    # warm mesh program (the host advances have nothing to compile)
    k = 0
    store.apply(_delta_batch(rng, ids, max(4, int(0.001 * m_live)), k))
    k += 1
    cur = store.capture()
    store._delta(ep, cur)
    for o in ops:
        warm[o.name] = store.analytics_advance(o, warm[o.name], cur)
    prev, last_batch = cur, None

    out = {"live_edges": int(m_live), "deltas": {}}
    for frac in FRACS:
        n = max(4, int(frac * m_live))
        rows = {o.name: dict(s=[], a=[], its=[], ita=[]) for o in ops}
        dms, nch, sms = [], [], []
        for _ in range(epochs):
            last_batch = _delta_batch(rng, ids, n, k)
            k += 1
            store.apply(last_batch)
            cur = store.capture()
            t0 = time.perf_counter()
            build_csr(cur)      # shared epoch infrastructure (both paths)
            sms.append((time.perf_counter() - t0) * 1e3)
            t0 = time.perf_counter()
            d, reason = store._delta(prev, cur)
            dms.append((time.perf_counter() - t0) * 1e3)
            assert reason == "ok", reason
            nch.append(sum(x.n_changed for x in d) if isinstance(d, list)
                       else d.n_changed)
            for o in ops:
                t0 = time.perf_counter()
                rs = store.analytics_result(o, cur)
                rows[o.name]["s"].append((time.perf_counter() - t0) * 1e3)
                t0 = time.perf_counter()
                ri = store.analytics_advance(o, warm[o.name], cur)
                rows[o.name]["a"].append((time.perf_counter() - t0) * 1e3)
                assert ri.mode == "incremental", (o.name, ri.reason)
                err = _max_err(rs.value, ri.value)
                assert err <= (1e-5 if o.name == "pagerank" else 0.0), \
                    (o.name, err)
                rows[o.name]["its"].append(rs.iters)
                rows[o.name]["ita"].append(ri.iters)
                warm[o.name] = ri
            prev = cur
        dmed = float(np.median(dms))
        per_op = {}
        for o in ops:
            r = rows[o.name]
            s = float(np.median(r["s"]))
            a = float(np.median(r["a"]))
            inc = a + dmed / len(ops)
            per_op[o.name] = {
                "scratch_ms": round(s, 3), "advance_ms": round(a, 3),
                "incremental_ms": round(inc, 3),
                "speedup": round(s / max(inc, 1e-6), 2),
                "iters_scratch": int(np.median(r["its"])),
                "iters_advance": int(np.median(r["ita"]))}
        out["deltas"][f"{100 * frac:g}%"] = {
            "delta_ops": n, "delta_changed": int(np.median(nch)),
            "snapshot_ms": round(float(np.median(sms)), 3),
            "delta_extract_ms": round(dmed, 3), "epochs": epochs,
            "per_op": per_op}

    # forced-fallback epoch: tombstone the previous batch's edges (they
    # exist, so the delta genuinely records deletes) — the monotone
    # advances must refuse with a recorded reason yet still answer right
    nd = max(2, len(last_batch.src) // 4)
    store.apply(OpBatch.edges(*_sym(last_batch.src[:nd],
                                    last_batch.dst[:nd],
                                    np.zeros(nd, np.float32))))
    cur = store.capture()
    fb = {}
    for o in ops:
        ri = store.analytics_advance(o, warm[o.name], cur)
        rs = store.analytics_result(o, cur)
        err = _max_err(rs.value, ri.value)
        assert err <= (1e-5 if o.name == "pagerank" else 0.0), (o.name, err)
        fb[o.name] = {"mode": ri.mode, "reason": ri.reason}
        warm[o.name] = ri
    for guarded in ("bfs", "wcc", "sssp"):
        assert fb[guarded]["mode"] == "scratch", fb[guarded]
        assert fb[guarded]["reason"], fb[guarded]
    out["fallback_epoch"] = fb
    out["store_stats"] = {kk: store.stats[kk] for kk in (
        "defrags", "defrag_ms", "defrag_host_ms", "defrag_sync_ms",
        "tiles_scanned", "ops_dropped")}
    return out


def _base_weights(rng, n: int) -> np.ndarray:
    """Base-load weights in [1.0, 2.0] — above every delta band."""
    return rng.uniform(1.0, 2.0, n).astype(np.float32)


def bench_local(n_vertices: int, base_ops: int, epochs: int, seed: int = 0,
                smoke: bool = False):
    from benchmarks.common import edge_stream
    from repro.api import OpBatch, make_store
    # sized to the workload, not the shared GRAPH_CAPS compile cache: the
    # per-epoch snapshot scan is O(pool capacity), and this bench records
    # per-epoch latency, so an oversized pool would tax BOTH paths
    kw = (dict(n_max=4096, pool_blocks=8192) if smoke else
          dict(n_max=16384, pool_blocks=32768))
    kw.update(block_size=16, k_max=256, batch=4096,
              dmax=4096 if smoke else 8192)  # symmetric hubs: 2x degree
    store = make_store("local", key_bits=32, expected_n=n_vertices,
                       undirected=False, m_cap=16384 if smoke else 262144,
                       max_delta_frac=0.25, **kw)
    src, dst, ids = edge_stream(n_vertices, base_ops, "powerlaw", seed)
    w = _base_weights(np.random.default_rng(seed + 5), base_ops)
    src, dst, w = _sym(src, dst, w)
    B = kw["batch"]
    for lo in range(0, len(src), B):
        store.apply(OpBatch.edges(src[lo:lo + B], dst[lo:lo + B],
                                  w[lo:lo + B]))
    assert not store.graph.overflowed
    res = run_chain(store, ids, epochs, seed)
    res["shards"] = 1
    return res


def _shard_worker(n_vertices: int, base_ops: int, epochs: int,
                  n_shards: int = 4, seed: int = 0, smoke: bool = False):
    """Runs inside the subprocess (placeholder devices already forced)."""
    from benchmarks.common import edge_stream
    from repro.api import OpBatch, make_store
    store = make_store(
        "sharded", n_shards=n_shards,
        n_per_shard=4 * max(1024, n_vertices),
        expected_n=max(256, n_vertices),
        pool_blocks=max(4096, 2 * n_vertices), block_size=16,
        k_max=256, dmax=8192, batch=4096,
        m_cap=8192 if smoke else 65536, max_delta_frac=0.25)
    src, dst, ids = edge_stream(n_vertices, base_ops, "powerlaw", seed)
    w = _base_weights(np.random.default_rng(seed + 5), base_ops)
    src, dst, w = _sym(src, dst, w)
    B = store.batch
    for lo in range(0, len(src), B):
        store.apply(OpBatch.edges(src[lo:lo + B], dst[lo:lo + B],
                                  w[lo:lo + B]))
    assert store.stats["ops_dropped"] == 0, store.stats
    res = run_chain(store, ids, epochs, seed)
    res["shards"] = n_shards
    return res


def bench_sharded(n_vertices: int, base_ops: int, epochs: int,
                  n_shards: int = 4, smoke: bool = False):
    """Spawn the worker under ``--xla_force_host_platform_device_count``."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count={n_shards}")
    env["PYTHONPATH"] = f"{ROOT / 'src'}:{env.get('PYTHONPATH', '')}"
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_analytics", "--_worker",
         json.dumps(dict(n_vertices=n_vertices, base_ops=base_ops,
                         epochs=epochs, n_shards=n_shards, smoke=smoke))],
        capture_output=True, text=True, env=env, cwd=str(ROOT), timeout=3600)
    for line in out.stdout.splitlines():
        if line.startswith("WORKER-RESULT "):
            return json.loads(line[len("WORKER-RESULT "):])
    raise RuntimeError(f"shard worker failed:\n{out.stderr[-3000:]}")


def _print_section(tag: str, res: dict):
    for fk, fr in res["deltas"].items():
        line = ", ".join(
            f"{name} {r['speedup']}x" for name, r in fr["per_op"].items())
        print(f"{tag} delta {fk} ({fr['delta_changed']} edges, extract "
              f"{fr['delta_extract_ms']} ms): {line}")
    fb = ", ".join(f"{n}:{v['mode']}({v['reason']})" if v["reason"] else
                   f"{n}:{v['mode']}" for n, v in
                   res["fallback_epoch"].items())
    print(f"{tag} tombstone epoch: {fb}")


def _gate_smoke(res: dict, tag: str):
    """CI gate: at the smallest delta, chaining must never lose — the
    amortized incremental path stays within 1.1x of scratch (+1 ms
    absolute slack, absorbing the ~free scalar ops whose scratch run is
    a single host read)."""
    small = res["deltas"][f"{100 * FRACS[0]:g}%"]["per_op"]
    for name, r in small.items():
        assert r["incremental_ms"] <= 1.1 * r["scratch_ms"] + 1.0, \
            (tag, name, r)


def run(smoke: bool = False):
    scale = SMOKE if smoke else FULL
    nv, base, epochs = scale["n_vertices"], scale["base_ops"], \
        scale["epochs"]
    # the sharded worker runs BEFORE this process touches JAX: a parent
    # holding the chip would starve its child
    four = bench_sharded(nv, base, epochs, smoke=smoke)
    one = bench_local(nv, base, epochs, smoke=smoke)
    _print_section("1-shard", one)
    _print_section("4-shard", four)
    if smoke:
        _gate_smoke(one, "one_shard")
        _gate_smoke(four, "four_shard")
    else:
        # the ROADMAP acceptance bar: warm-start PageRank/WCC at small
        # deltas beats scratch by >= 5x on the 1-shard backend
        for fk in (f"{100 * FRACS[0]:g}%", f"{100 * FRACS[1]:g}%"):
            for name in ("pagerank", "wcc"):
                sp = one["deltas"][fk]["per_op"][name]["speedup"]
                mark = "OK" if sp >= 5 else "BELOW-BAR"
                print(f"[{mark}] {name} @ {fk}: {sp}x")

    results = {"one_shard": one, "four_shard": four}
    doc = {}
    if OUT.exists():
        doc = json.loads(OUT.read_text())
    doc.setdefault("bench", "analytics")
    if smoke:
        doc["smoke"] = dict(graph=dict(scale, dist="powerlaw"), **results)
    else:
        doc["scale"] = "full"
        doc["graph"] = dict(scale, dist="powerlaw")
        doc.update(results)
    OUT.write_text(json.dumps(doc, indent=1) + "\n")
    print(f"[OK] wrote {OUT} ({'smoke' if smoke else 'full'})")
    return doc


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--_worker", help="internal: JSON kwargs for the "
                    "in-subprocess shard worker")
    args = ap.parse_args(argv)
    if args._worker:
        res = _shard_worker(**json.loads(args._worker))
        print("WORKER-RESULT " + json.dumps(res))
        return res
    return run(smoke=args.smoke)


if __name__ == "__main__":
    from repro.runtime import init_compile_cache
    init_compile_cache()
    main()
