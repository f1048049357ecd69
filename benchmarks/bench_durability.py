"""Durability overhead harness: WAL group-commit cost, checkpoint
full-vs-delta cost, and recovery time, recorded in ``BENCH_durability.json``.

Three questions, one artifact:

* **WAL tax** — the same powerlaw ingest stream through a bare
  ``LocalStore`` and through ``DurableStore`` at group-commit 1 / 8 / 32
  / 256 (1 = fsync every batch, the paranoid setting; 256 ≈ free). The
  ratio column is the headline: the default (32) must stay within 30% of
  the WAL-off throughput (CI gate in ``--smoke``).
* **checkpoint cost** — a full checkpoint of the loaded store vs an
  incremental one after a short additional stream: wall ms and on-disk
  bytes for each, plus the delta's touched-block count.
* **recovery** — wall time of ``recover()`` (checkpoint chain + WAL
  suffix replay) and a bit-exactness flag against the uninterrupted
  store's epoch snapshot.

    PYTHONPATH=src python -m benchmarks.bench_durability --record after
    PYTHONPATH=src python -m benchmarks.bench_durability --smoke   # CI
"""
from __future__ import annotations

import argparse
import json
import pathlib
import shutil
import tempfile
import time

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parents[1]
OUT = ROOT / "BENCH_durability.json"

FULL = dict(n_vertices=8192, n_ops=65536, batch=4096, tail_ops=8192)
SMOKE = dict(n_vertices=512, n_ops=8192, batch=1024, tail_ops=2048)

GROUP_COMMITS = (1, 8, 32, 256)
DEFAULT_GC = 32


def _store(n_vertices: int, batch: int):
    from benchmarks.common import GRAPH_CAPS
    from repro.api import make_store
    kw = dict(GRAPH_CAPS)
    kw["batch"] = batch
    return make_store("local", key_bits=32, expected_n=n_vertices,
                      undirected=False, **kw)


def _ingest(store, src, dst, w, batch):
    from repro.api import OpBatch
    t0 = time.perf_counter()
    for lo in range(0, len(src), batch):
        store.apply(OpBatch.edges(src[lo:lo + batch], dst[lo:lo + batch],
                                  w[lo:lo + batch]))
    return time.perf_counter() - t0


def _stream(n_vertices: int, n_ops: int, seed: int = 0):
    from benchmarks.common import edge_stream
    src, dst, _ = edge_stream(n_vertices, n_ops, "powerlaw", seed)
    w = np.random.default_rng(seed + 1).uniform(
        0.5, 2.0, n_ops).astype(np.float32)
    return src, dst, w


def _snapshot_leaves(store):
    import jax
    from repro.api import ReadOp
    snap = store.read(ReadOp("snapshot"))
    return [np.asarray(x) for x in jax.tree.leaves(snap)]


def bench_wal(nv: int, n_ops: int, batch: int):
    """WAL-off vs WAL-on throughput at each group-commit setting (same
    stream, warm batches excluded so jit compilation stays out)."""
    from repro.api import OpBatch
    from repro.storage import DurableStore

    warm = 2 * batch
    src, dst, w = _stream(nv, n_ops + warm)
    out = {}

    base = _store(nv, batch)
    for lo in (0, batch):
        base.apply(OpBatch.edges(src[lo:lo + batch], dst[lo:lo + batch],
                                 w[lo:lo + batch]))
    dt = _ingest(base, src[warm:], dst[warm:], w[warm:], batch)
    out["wal_off"] = {"seconds": round(dt, 3),
                      "updates_per_s": round(n_ops / dt, 1)}
    print(f"WAL off          : {n_ops / dt:10.0f} updates/s")

    for gc in GROUP_COMMITS:
        d = tempfile.mkdtemp(prefix=f"bench_dur_gc{gc}_")
        store = DurableStore(_store(nv, batch), d, group_commit=gc)
        for lo in (0, batch):
            store.apply(OpBatch.edges(src[lo:lo + batch],
                                      dst[lo:lo + batch],
                                      w[lo:lo + batch]))
        dt = _ingest(store, src[warm:], dst[warm:], w[warm:], batch)
        store.sync()
        r = {"seconds": round(dt, 3),
             "updates_per_s": round(n_ops / dt, 1),
             "vs_wal_off": round(out["wal_off"]["seconds"] / dt, 3),
             "wal_bytes": store.stats["wal_bytes"],
             "wal_syncs": store.stats["wal_syncs"]}
        out[f"group_commit_{gc}"] = r
        print(f"WAL gc={gc:<4d}     : {n_ops / dt:10.0f} updates/s "
              f"({r['vs_wal_off']:.2f}x of WAL-off, {r['wal_syncs']} "
              f"fsyncs, {r['wal_bytes']} bytes)")
        store.close()
        shutil.rmtree(d, ignore_errors=True)
    return out


def bench_checkpoint_and_recovery(nv: int, n_ops: int, batch: int,
                                  tail_ops: int):
    """Checkpoint full vs delta cost on a loaded store, then recovery
    wall time + bit-exactness (checkpoint chain + WAL suffix replay)."""
    from repro.api import OpBatch, ReadOp, make_store  # noqa: F401
    from repro.storage import DurableStore, recover

    d = tempfile.mkdtemp(prefix="bench_dur_ckpt_")
    src, dst, w = _stream(nv, n_ops + 2 * tail_ops)
    store = DurableStore(_store(nv, batch), d, group_commit=DEFAULT_GC)
    _ingest(store, src[:n_ops], dst[:n_ops], w[:n_ops], batch)

    t0 = time.perf_counter()
    man_full = store.checkpoint()
    full_ms = (time.perf_counter() - t0) * 1000.0
    assert man_full["kind"] == "full"

    lo = n_ops
    _ingest(store, src[lo:lo + tail_ops], dst[lo:lo + tail_ops],
            w[lo:lo + tail_ops], batch)
    t0 = time.perf_counter()
    man_delta = store.checkpoint()
    delta_ms = (time.perf_counter() - t0) * 1000.0

    # WAL suffix beyond the last checkpoint, so recovery has replaying
    # to do on top of the chain
    lo = n_ops + tail_ops
    _ingest(store, src[lo:lo + tail_ops], dst[lo:lo + tail_ops],
            w[lo:lo + tail_ops], batch)
    store.sync()
    live_leaves = _snapshot_leaves(store)
    live_edges = store.read(ReadOp("num_edges"))
    store.close()

    t0 = time.perf_counter()
    rec, report = recover(d, lambda: _store(nv, batch))
    recover_s = time.perf_counter() - t0
    bit_exact = (rec.read(ReadOp("num_edges")) == live_edges and
                 all(np.array_equal(a, b) for a, b in
                     zip(live_leaves, _snapshot_leaves(rec))))
    rec.close()
    shutil.rmtree(d, ignore_errors=True)
    out = {
        "full": {"ms": round(full_ms, 1), "bytes": man_full["bytes"]},
        "delta": {"ms": round(delta_ms, 1), "bytes": man_delta["bytes"],
                  "kind": man_delta["kind"],
                  "touched_blocks": (man_delta.get("delta") or {}).get(
                      "n_blocks"),
                  "vs_full_bytes": round(
                      man_delta["bytes"] / man_full["bytes"], 3)},
        "recovery": {"seconds": round(recover_s, 3),
                     "replayed": report["replayed"],
                     "checkpoint_kind": report["checkpoint_kind"],
                     "bit_exact": bool(bit_exact)},
    }
    print(f"checkpoint full  : {full_ms:.0f} ms, {man_full['bytes']} B")
    print(f"checkpoint delta : {delta_ms:.0f} ms, {man_delta['bytes']} B "
          f"({out['delta']['vs_full_bytes']:.2f}x of full, "
          f"kind={man_delta['kind']})")
    print(f"recovery         : {recover_s:.3f} s "
          f"({report['checkpoint_kind']} ckpt + {report['replayed']} "
          f"records), bit_exact={bit_exact}")
    assert bit_exact, "recovered store diverged from the live one"
    return out


def run(smoke: bool = False, record: str = "after"):
    scale = SMOKE if smoke else FULL
    results = {"wal": bench_wal(scale["n_vertices"], scale["n_ops"],
                                scale["batch"]),
               "checkpoint": bench_checkpoint_and_recovery(
                   scale["n_vertices"], scale["n_ops"], scale["batch"],
                   scale["tail_ops"])}
    ratio = results["wal"][f"group_commit_{DEFAULT_GC}"]["vs_wal_off"]
    results["wal"]["default_group_commit"] = DEFAULT_GC
    results["wal"]["default_vs_wal_off"] = ratio
    if smoke:
        # CI gate (ISSUE 10 acceptance): WAL-on at the default
        # group-commit must keep >= 0.7x of WAL-off throughput
        assert ratio >= 0.7, \
            f"WAL-on at gc={DEFAULT_GC} is {ratio:.2f}x of WAL-off (< 0.7)"

    doc = {}
    if OUT.exists():
        doc = json.loads(OUT.read_text())
    doc.setdefault("bench", "durability")
    if smoke:
        doc["smoke"] = dict(stream=scale, **results)
    else:
        doc["scale"] = "full"
        doc["stream"] = scale
        doc[record] = results
    OUT.write_text(json.dumps(doc, indent=1) + "\n")
    print(f"[OK] wrote {OUT} ({'smoke' if smoke else record})")
    return doc


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--record", choices=("before", "after"),
                    default="after")
    args = ap.parse_args(argv)
    return run(smoke=args.smoke, record=args.record)


if __name__ == "__main__":
    from repro.runtime import init_compile_cache
    init_compile_cache()
    main()
