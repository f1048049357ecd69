"""Ingest fast-path throughput harness (updates/sec, fig8-style streams).

Measures steady-state edge-update throughput through the unified
``repro.api.GraphStore`` front door for batched streams on

* the 1-shard ``LocalStore`` (jitted padded batches), and
* the 4-shard ``ShardedStore`` (subprocess with placeholder devices:
  route -> all_to_all -> apply, one fused SPMD program per batch),

at a small and a large batch size, and records the numbers in
``BENCH_ingest.json`` at the repo root.  Three stream shapes:

* ``insert``  — plain powerlaw inserts (the historical before/after
  trajectory every write-path PR has to beat);
* ``mixed``   — fig9-style insert/update/delete stream (25% tombstones,
  powerlaw endpoints repeat, so updates occur naturally) exercising the
  probe's delete accounting under load;
* ``hub``     — hub-heavy stream where every batch overflows MANY
  over-window (tier-L) vertices: with more than ``k_big`` of them the
  fast path falls back to a global defrag (amortized-correct, recorded
  via the pool's ``defrags`` counter), while a raised ``k_big`` keeps the
  stream on the fast path — the knob trade the ROADMAP asks to record.

    PYTHONPATH=src python -m benchmarks.bench_ingest --record after
    PYTHONPATH=src python -m benchmarks.bench_ingest --smoke   # CI artifact

``--record before`` is only used once per optimization PR, on the pre-change
tree; ``--record after`` (the default) refreshes the after section in place.
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
OUT = ROOT / "BENCH_ingest.json"

# one jit cache across batch configs would need one batch size; each config
# builds its own graph, so keep the stream modest and let compile warm out.
# ``hub_ops`` gives the hub stream enough batches that every k_big budget
# below the hub count pays at least one overflow defrag (the smoke job
# asserts it — the spike path must actually run in CI).
FULL = dict(n_vertices=8192, n_ops=65536, hub_ops=65536, hub_n_hubs=48,
            hub_k_big=(16, 64))
SMOKE = dict(n_vertices=512, n_ops=4096, hub_ops=12288, hub_n_hubs=24,
             hub_k_big=(16, 64))


def _throughput(n_ops: int, dt: float) -> float:
    return round(n_ops / dt, 1)


def _latency_stats(lat: np.ndarray) -> dict:
    """Per-batch wall-time percentiles (ms) — the spike metric: a
    triggered defrag shows up as the gap between p50 and p99/max."""
    ms = np.asarray(lat) * 1000.0
    return {"p50_ms": round(float(np.percentile(ms, 50)), 2),
            "p99_ms": round(float(np.percentile(ms, 99)), 2),
            "max_ms": round(float(ms.max()), 2)}


def _batched_apply(store, src, dst, w, batch):
    """Apply the stream one device batch per call, timing each batch."""
    from repro.api import OpBatch
    lat = []
    for lo in range(0, len(src), batch):
        t0 = time.perf_counter()
        res = store.apply(OpBatch.edges(
            src[lo:lo + batch], dst[lo:lo + batch],
            None if w is None else w[lo:lo + batch]))
        lat.append(time.perf_counter() - t0)
        assert res.dropped == 0
    return np.asarray(lat)


def _mixed_weights(n: int, seed: int = 1) -> np.ndarray:
    """fig9-style op mix: uniform weights, 25% NULL tombstones (deletes);
    powerlaw endpoint reuse supplies the updates."""
    rng = np.random.default_rng(seed)
    w = rng.uniform(0.5, 2.0, n).astype(np.float32)
    w[rng.random(n) < 0.25] = 0.0
    return w


def _hub_stream(n_vertices: int, n_ops: int, n_hubs: int, seed: int = 0):
    """Every op's source is one of ``n_hubs`` hubs (round-robin, so each
    batch touches every hub): hub edge arrays quickly outgrow the probe
    window and overflow per batch — the tier-L (k_big) stress shape."""
    rng = np.random.default_rng(seed)
    ids = rng.choice(2 ** 32, n_vertices, replace=False).astype(np.uint64)
    hubs = ids[:n_hubs]
    src = hubs[np.arange(n_ops) % n_hubs]
    dst = ids[rng.integers(0, n_vertices, n_ops)]
    return src, dst, ids


def _local_store(n_vertices: int, batch: int, **over):
    from benchmarks.common import GRAPH_CAPS
    from repro.api import make_store
    kw = dict(GRAPH_CAPS)
    kw["batch"] = batch
    kw.update(over)
    return make_store("local", key_bits=32, expected_n=n_vertices,
                      undirected=False, **kw)


def bench_single(n_vertices: int, n_ops: int, batch: int, seed: int = 0,
                 weights=None, **store_over):
    """1-shard ingest: a batched powerlaw stream through ``LocalStore``,
    timed per device batch so the latency PERCENTILES (not just the mean
    throughput) are a recorded artifact."""
    from benchmarks.common import edge_stream
    from repro.api import OpBatch, ReadOp

    warm = 2 * batch   # batch 1 compiles the non-donating program (fresh
    #                    states are donation-pinned), batch 2 the donated
    #                    steady-state executable — both stay out of timing
    src, dst, _ = edge_stream(n_vertices, n_ops + warm, "powerlaw", seed)
    w = weights(n_ops + warm) if weights is not None else None
    store = _local_store(n_vertices, batch, **store_over)
    for lo in (0, batch):
        store.apply(OpBatch.edges(src[lo:lo + batch], dst[lo:lo + batch],
                                  None if w is None else w[lo:lo + batch]))
    lat = _batched_apply(store, src[warm:], dst[warm:],
                         None if w is None else w[warm:], batch)
    dt = float(lat.sum())
    assert not store.graph.overflowed
    return {"batch": batch, "ops": n_ops, "seconds": round(dt, 3),
            "updates_per_s": _throughput(n_ops, dt),
            **_latency_stats(lat),
            "tiles_scanned": store.stats["tiles_scanned"],
            "live_edges": store.read(ReadOp("num_edges"))}


def bench_hub(n_vertices: int, n_ops: int, batch: int, n_hubs: int,
              k_big: int, seed: int = 0, defrag_impl: str = "auto"):
    """Hub-heavy tier-L stress: same stream at two ``k_big`` budgets —
    the small one records overflow-defrag fallbacks (and their wall-time
    spike via ``defrag_ms`` / the p99-over-p50 gap), the raised one stays
    on the fast path (each unit of k_big costs one dmax-width compaction
    row per batch)."""
    from repro.api import OpBatch, ReadOp

    warm = 2 * batch   # both program variants compile out of the timing
    src, dst, _ = _hub_stream(n_vertices, n_ops + warm, n_hubs, seed)
    store = _local_store(n_vertices, batch, k_big=k_big,
                         defrag_impl=defrag_impl)
    for lo in (0, batch):
        store.apply(OpBatch.edges(src[lo:lo + batch], dst[lo:lo + batch]))
    d0 = store.graph.num_defrags
    lat = _batched_apply(store, src[warm:], dst[warm:], None, batch)
    dt = float(lat.sum())
    assert not store.graph.overflowed
    return {"batch": batch, "ops": n_ops, "n_hubs": n_hubs,
            "k_big": k_big, "seconds": round(dt, 3),
            "updates_per_s": _throughput(n_ops, dt),
            **_latency_stats(lat),
            "overflow_defrags": store.graph.num_defrags - d0,
            "defrag_ms": round(store.graph.defrag_ms, 1),
            # the spike decomposed: host staging (python + dispatch) vs
            # the blocked-on-device sync at the rebuild boundary
            "defrag_host_ms": round(store.graph.defrag_host_ms, 1),
            "defrag_sync_ms": round(store.graph.defrag_sync_ms, 1),
            "tiles_scanned": store.stats["tiles_scanned"],
            "live_edges": store.read(ReadOp("num_edges"))}


def bench_defrag(n_vertices: int, n_ops: int, batch: int, n_hubs: int,
                 seed: int = 0, iters: int = 3):
    """Explicit-rebuild microbench: the SAME hub-loaded state rebuilt by
    the dense entry-scatter reference and by the streaming block-row
    path — the before/after of the defrag spike, isolated from the
    ingest around it (``k_big`` is raised so loading never rebuilds)."""
    import jax

    from repro.api import OpBatch
    from repro.core import radixgraph as rg

    out = {}
    for impl in ("dense", "stream"):
        store = _local_store(n_vertices, batch, k_big=64, defrag_impl=impl)
        src, dst, _ = _hub_stream(n_vertices, n_ops, n_hubs, seed)
        _batched_apply(store, src, dst, None, batch)
        g = store.graph
        st = g.state
        r = rg._defrag(g.sort_spec, g.pool_spec, st)   # compile + warm
        jax.block_until_ready(r)
        ts = []
        for _ in range(iters):
            t0 = time.perf_counter()
            r = rg._defrag(g.sort_spec, g.pool_spec, st)
            jax.block_until_ready(r)
            ts.append(time.perf_counter() - t0)
        out[impl] = {"seconds": round(float(np.median(ts)), 3),
                     "defrags_during_load": g.num_defrags}
    out["speedup"] = round(out["dense"]["seconds"] /
                           max(out["stream"]["seconds"], 1e-9), 1)
    return out


def _shard_worker(n_vertices: int, n_ops: int, batch: int, n_shards: int,
                  seed: int = 0, mixed: bool = False, pipeline: int = 8):
    """Runs inside the subprocess (placeholder devices already forced).

    ``pipeline`` is the flush depth: each ``store.apply`` stages
    ``pipeline`` device batches and dispatches them back-to-back (donated
    steady-state buffers, a single host sync per flush). It is capped at
    the stream's batch count so short (smoke) streams never retrace a
    ragged depth inside the timed region."""
    import jax

    from benchmarks.common import edge_stream
    from repro.api import OpBatch, make_store

    pipeline = max(1, min(pipeline, n_ops // batch))
    store = make_store(
        "sharded", n_shards=n_shards,
        n_per_shard=4 * max(1024, n_vertices),
        expected_n=max(256, n_vertices),
        pool_blocks=max(4096, 16 * n_vertices), block_size=16,
        k_max=256, dmax=4096, batch=batch, pipeline_depth=pipeline,
        sync_incremental=False)     # measure the raw routed-apply path

    chunk = pipeline * batch        # ops per flush (one apply call)
    warm = 2 * chunk                # see below
    src, dst, _ = edge_stream(n_vertices, n_ops + warm, "powerlaw", seed)
    w = _mixed_weights(n_ops + warm) if mixed else \
        np.ones(n_ops + warm, np.float32)

    # warm BOTH program variants before timing: the first dispatch runs the
    # non-donating program (fresh states are donation-pinned), every later
    # one the donated executable — a separate compile that must not land in
    # the timed region (it did once: ~12s mistaken for steady-state cost)
    for lo in range(0, warm, chunk):
        store.apply(OpBatch.edges(src[lo:lo + chunk], dst[lo:lo + chunk],
                                  w[lo:lo + chunk]))
    jax.block_until_ready(store.state)
    for k in ("flushes", "super_batches", "host_stage_ms", "flush_wait_ms"):
        store.stats[k] = 0          # report the timed region only
    t0 = time.perf_counter()
    for lo in range(warm, n_ops + warm, chunk):
        store.apply(OpBatch.edges(src[lo:lo + chunk], dst[lo:lo + chunk],
                                  w[lo:lo + chunk]))
    jax.block_until_ready(store.state)
    dt = time.perf_counter() - t0
    assert store.stats["ops_dropped"] == 0, store.stats
    sb = max(1, store.stats["super_batches"])
    return {"batch": batch, "ops": n_ops, "seconds": round(dt, 3),
            "updates_per_s": _throughput(n_ops, dt), "shards": n_shards,
            "tiles_scanned": store.stats["tiles_scanned"],
            "defrags": store.stats["defrags"],
            "defrag_host_ms": store.stats["defrag_host_ms"],
            "defrag_sync_ms": store.stats["defrag_sync_ms"],
            "pipeline_depth": pipeline,
            "flushes": store.stats["flushes"],
            "super_batches": store.stats["super_batches"],
            # per-super-batch host-clock breakdown: the stage side is
            # python staging + async dispatch, the wait side the rest of
            # the wall time, the once-per-flush wait for the drop counts
            "host_ms_per_super_batch": round(
                store.stats["host_stage_ms"] / sb, 2),
            "flush_wait_ms_per_super_batch": round(
                (dt * 1000.0 - store.stats["host_stage_ms"]) / sb, 2),
            "kind": "mixed" if mixed else "insert"}


def bench_sharded(n_vertices: int, n_ops: int, batch: int, n_shards: int = 4,
                  mixed: bool = False, pipeline: int = 8):
    """Spawn the worker under ``--xla_force_host_platform_device_count``."""
    env = dict(os.environ)
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                        f" --xla_force_host_platform_device_count={n_shards}")
    env["PYTHONPATH"] = f"{ROOT / 'src'}:{env.get('PYTHONPATH', '')}"
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.bench_ingest", "--_worker",
         json.dumps(dict(n_vertices=n_vertices, n_ops=n_ops, batch=batch,
                         n_shards=n_shards, mixed=mixed, pipeline=pipeline))],
        capture_output=True, text=True, env=env, cwd=str(ROOT), timeout=1800)
    for line in out.stdout.splitlines():
        if line.startswith("WORKER-RESULT "):
            return json.loads(line[len("WORKER-RESULT "):])
    raise RuntimeError(f"shard worker failed:\n{out.stderr[-2000:]}")


def run(smoke: bool = False, record: str = "after"):
    scale = SMOKE if smoke else FULL
    nv, no = scale["n_vertices"], scale["n_ops"]
    batches = (1024, 4096)
    results = {"one_shard": {}, "four_shard": {}, "mixed": {}, "hub": {},
               "pipeline": {}}
    # every sharded cell runs in a worker process BEFORE this process
    # touches JAX: a parent holding the chip would starve its children
    for b in batches:
        r = bench_sharded(nv, no, b)
        results["four_shard"][f"B{b}"] = r
        print(f"4-shard  B={b}: {r['updates_per_s']:.0f} updates/s "
              f"({r['ops']} ops in {r['seconds']}s)")
    # fig9-style mixed insert/update/delete trajectory (4- and 1-shard)
    r = bench_sharded(nv, no, 4096, mixed=True)
    results["mixed"]["four_shard_B4096"] = r
    print(f"mixed 4-shard  B=4096: {r['updates_per_s']:.0f} updates/s")
    # the pipelined-path depth sweep: the SAME 4-shard stream at K=1 (one
    # host sync per batch — the PR-5 shape) vs K=8 (8 donated dispatches
    # per flush sync), with the per-super-batch host/device breakdown
    pb = 512 if smoke else 4096
    for K in (1, 8):
        r = bench_sharded(nv, no, pb, pipeline=K)
        results["pipeline"][f"K{K}"] = r
        print(f"pipeline K={K} B={pb}: {r['updates_per_s']:.0f} updates/s "
              f"({r['super_batches']} super-batches, host "
              f"{r['host_ms_per_super_batch']} ms / flush wait "
              f"{r['flush_wait_ms_per_super_batch']} ms per super-batch)")
    for b in batches:
        r = bench_single(nv, no, b)
        results["one_shard"][f"B{b}"] = r
        print(f"1-shard  B={b}: {r['updates_per_s']:.0f} updates/s "
              f"({r['ops']} ops in {r['seconds']}s)")
    r = bench_single(nv, no, 4096, weights=_mixed_weights)
    results["mixed"]["one_shard_B4096"] = r
    print(f"mixed 1-shard  B=4096: {r['updates_per_s']:.0f} updates/s "
          f"({r['live_edges']} live edges)")
    k1 = results["pipeline"]["K1"]["updates_per_s"]
    k8 = results["pipeline"]["K8"]["updates_per_s"]
    results["pipeline"]["speedup_K8_over_K1"] = round(k8 / k1, 2)
    if smoke:
        # CI gate: the deep pipeline must not be slower than per-batch
        # flushing (5% floor absorbs single-core scheduling noise)
        assert k8 >= 0.95 * k1, results["pipeline"]
    # hub-heavy tier-L budget: small k_big falls back to defrag, raised
    # k_big rides the fast path — record both sides of the knob, plus the
    # per-batch latency spike the triggered rebuilds cost
    for kb in scale["hub_k_big"]:
        r = bench_hub(nv, scale["hub_ops"], 4096, scale["hub_n_hubs"], kb)
        results["hub"][f"k_big{kb}"] = r
        print(f"hub({scale['hub_n_hubs']} hubs) k_big={kb}: "
              f"{r['updates_per_s']:.0f} updates/s, "
              f"{r['overflow_defrags']} overflow defrags "
              f"({r['defrag_ms']} ms), p50 {r['p50_ms']} / "
              f"p99 {r['p99_ms']} ms")
        if smoke and kb < scale["hub_n_hubs"]:
            # the CI smoke must actually exercise the overflow-defrag
            # path — a budget below the hub count has to rebuild
            assert r["overflow_defrags"] >= 1, r
    # the defrag spike itself, dense reference vs streaming rebuild
    r = bench_defrag(nv, scale["hub_ops"], 4096, scale["hub_n_hubs"])
    results["defrag"] = r
    print(f"defrag: dense {r['dense']['seconds']}s vs stream "
          f"{r['stream']['seconds']}s ({r['speedup']}x)")

    doc = {}
    if OUT.exists():
        doc = json.loads(OUT.read_text())
    doc.setdefault("bench", "ingest")
    if smoke:
        # CI sanity record: never clobbers the committed full-scale
        # before/after trajectory
        doc["smoke"] = dict(stream=dict(scale, dist="powerlaw"), **results)
    else:
        doc["scale"] = "full"
        doc["stream"] = dict(scale, dist="powerlaw")
        doc[record] = results
    OUT.write_text(json.dumps(doc, indent=1) + "\n")
    print(f"[OK] wrote {OUT} ({'smoke' if smoke else record})")
    return doc


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--record", choices=("before", "after"), default="after")
    ap.add_argument("--_worker", help="internal: JSON kwargs for the "
                    "in-subprocess shard worker")
    args = ap.parse_args(argv)
    if args._worker:
        res = _shard_worker(**json.loads(args._worker))
        print("WORKER-RESULT " + json.dumps(res))
        return res
    return run(smoke=args.smoke, record=args.record)


if __name__ == "__main__":
    from repro.runtime import init_compile_cache
    init_compile_cache()
    main()
