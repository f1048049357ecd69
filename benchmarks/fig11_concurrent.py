"""Paper Fig. 11: concurrent reads & writes — through the graph query service.

Thread-scaling becomes shard-scaling on the SPMD substrate, and the mixed
workload runs end-to-end through ``repro.api``: a ``ShardedStore`` feeds
``serve.GraphQueryService`` (writer ingests micro-batches, owner-routed
degree reads answer against sealed epochs, 1:1 interleave — the paper's
concurrent workload). After the stream drains, distributed BFS/PageRank
answers from the service are validated against a ``LocalStore`` running
the SAME AnalyticsOps — one API, two backends, dict-equal results (a
mismatch raises).

In-process runs measure the 1-shard configuration; multi-shard points run in
a subprocess with ``XLA_FLAGS=--xla_force_host_platform_device_count``:

  PYTHONPATH=src python -m benchmarks.fig11_concurrent            # 1 + 4 shards
  PYTHONPATH=src python -m benchmarks.fig11_concurrent --shards 2 # one config
"""
from __future__ import annotations

import argparse
import os
import pathlib
import subprocess
import sys

import numpy as np

from .common import edge_stream, emit

HEADER = ("fig11", "shards", "write_Mops", "read_Mqps", "bfs_ok", "pr_err")
REPO = pathlib.Path(__file__).resolve().parents[1]


def run_one(shards: int, scale: float = 1.0, validate: bool = True):
    from repro.api import AnalyticsOp, OpBatch, make_store
    from repro.serve.graph_service import (GraphQueryService,
                                           drive_mixed_workload)

    n_v = max(256, int(1024 * scale))
    n_e = max(2048, int(16384 * scale))
    rng = np.random.default_rng(0)
    src, dst, ids = edge_stream(n_v, n_e, "powerlaw", seed=0)
    w = rng.uniform(0.5, 2, n_e).astype(np.float32)

    store = make_store("sharded", n_shards=shards, n_per_shard=8192,
                       expected_n=4096, pool_blocks=16384, block_size=16,
                       dmax=2048, k_max=128, batch=1024 * shards,
                       query_batch=256 * shards)
    svc = GraphQueryService(store, bfs_iters=32, pr_iters=20)

    qids = ids[:min(256 * shards, n_v)]
    dt, reads = drive_mixed_workload(svc, src, dst, w, qids)
    assert svc.stats["ops_dropped"] == 0

    tb = svc.submit_query("bfs", source=int(src[0]))
    tp = svc.submit_query("pagerank")
    svc.run()
    res = {tb: svc.claim(tb), tp: svc.claim(tp)}

    bfs_ok, pr_err = True, 0.0
    if validate:
        ref = make_store("local", n_max=4 * n_v, key_bits=32,
                         expected_n=n_v, batch=1024, pool_blocks=32768,
                         block_size=16, dmax=2048, k_max=128)
        ref.apply(OpBatch.edges(src, dst, w))
        ref_d = ref.analytics(AnalyticsOp("bfs", {"source": int(src[0]),
                                                  "max_iters": 32}))
        ref_pr = ref.analytics(AnalyticsOp("pagerank", {"iters": 20}))
        bfs_ok = res[tb] == ref_d       # same live-vertex keys, same depths
        assert set(res[tp]) == set(ref_pr)
        pr_err = max(abs(res[tp][v] - ref_pr[v]) for v in ref_pr) \
            if ref_pr else 0.0
        assert bfs_ok, "sharded BFS diverged from single-shard reference"
        assert pr_err < 1e-4, \
            f"sharded PageRank diverged from reference (max err {pr_err})"

    return [("fig11", shards, round(n_e / dt / 1e6, 5),
             round(reads / dt / 1e6, 5), bfs_ok, f"{pr_err:.2e}")]


def _subprocess_rows(shards: int, scale: float):
    env = {**os.environ,
           "XLA_FLAGS": f"--xla_force_host_platform_device_count={shards}",
           "PYTHONPATH": "src"}
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.fig11_concurrent",
         "--shards", str(shards), "--scale", str(scale)],
        capture_output=True, text=True, env=env, cwd=str(REPO), timeout=1800)
    if out.returncode != 0:
        raise RuntimeError(f"fig11 {shards}-shard subprocess failed:\n"
                           + out.stderr[-2000:])
    return [tuple(ln.split(",")) for ln in out.stdout.splitlines()
            if ln.startswith("fig11,")]


def run(scale: float = 1.0):
    # the 4-shard subprocess runs BEFORE this process touches JAX: a parent
    # holding the chip would starve its child
    four = _subprocess_rows(4, scale)
    return emit([HEADER] + run_one(1, scale) + four)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--shards", type=int, default=None,
                    help="run ONE config in-process (the parent sets "
                         "placeholder devices via XLA_FLAGS)")
    ap.add_argument("--scale", type=float, default=1.0)
    args = ap.parse_args(argv)
    if args.shards is None:
        run(args.scale)
    else:
        emit(run_one(args.shards, args.scale))


if __name__ == "__main__":
    from repro.runtime import init_compile_cache
    init_compile_cache()
    main()
