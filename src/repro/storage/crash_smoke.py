"""Crash-recovery smoke: kill a real ingest subprocess, recover, assert
bit-exact parity against an uninterrupted control run.

Protocol (one command, used by CI and the slow test):

1. the parent builds a deterministic edge stream (seeded) and picks a
   kill batch;
2. a CHILD process ingests the stream through a ``DurableStore``
   (group-commit WAL + periodic incremental checkpoints) and SIGKILLs
   itself right after applying the kill batch — unsynced group-commit
   tail and all, exactly like a power cut;
3. the parent recovers from the directory, derives how many batches
   survived (the recovery report's ``last_seq``), replays the control
   store to that same prefix, and asserts the epoch CSR snapshot,
   ``num_edges`` and a PageRank run are bit-exact;
4. the parent then finishes the stream on the RECOVERED store and
   asserts final parity with the full control run — restart + replay
   loses nothing but the unsynced tail.

    PYTHONPATH=src python -m repro.storage.crash_smoke --seed 7

Exit code 0 = every assertion held. ``--json`` prints the summary
record (the CI step uploads it next to the bench artifacts).
"""
from __future__ import annotations

import argparse
import json
import os
import pathlib
import signal
import subprocess
import sys
import tempfile

import numpy as np

CAPS = dict(n_max=4096, expected_n=2048, pool_blocks=8192, block_size=16,
            k_max=128, dmax=1024, batch=512)


def _stream(seed: int, n_ops: int, batch: int):
    """Deterministic mixed insert/delete batches (shared parent/child)."""
    from repro.api import OpBatch
    rng = np.random.default_rng(seed)
    ids = rng.choice(2 ** 24, CAPS["n_max"] // 2,
                     replace=False).astype(np.uint64)
    out = []
    for lo in range(0, n_ops, batch):
        n = min(batch, n_ops - lo)
        w = rng.uniform(0.5, 2.0, n).astype(np.float32)
        w[rng.random(n) < 0.05] = 0.0        # tombstones ride along
        out.append(OpBatch.edges(rng.choice(ids, n), rng.choice(ids, n),
                                 w))
    return out


def _mk_store():
    from repro.api import make_store
    return make_store("local", **CAPS)


def _child(args) -> int:
    from repro.storage import DurableStore
    batches = _stream(args.seed, args.ops, args.batch)
    store = DurableStore(_mk_store(), args.dir,
                         group_commit=args.group_commit,
                         checkpoint_every=max(2, len(batches) // 3))
    for i, b in enumerate(batches):
        store.apply(b)
        if i == args.kill_batch:
            os.kill(os.getpid(), signal.SIGKILL)   # no flush, no goodbye
    return 0


def _snapshot_sig(store):
    import jax
    from repro.api import AnalyticsOp, ReadOp
    snap = store.read(ReadOp("snapshot"))
    leaves = [np.asarray(x) for x in jax.tree.leaves(snap)]
    pr = store.analytics(AnalyticsOp("pagerank", {"iters": 10}))
    return dict(num_edges=store.read(ReadOp("num_edges")), leaves=leaves,
                pagerank=pr)


def _assert_sig_equal(a: dict, b: dict, where: str):
    assert a["num_edges"] == b["num_edges"], \
        f"{where}: num_edges {a['num_edges']} != {b['num_edges']}"
    for i, (x, y) in enumerate(zip(a["leaves"], b["leaves"])):
        assert np.array_equal(x, y), f"{where}: snapshot leaf {i} differs"
    assert a["pagerank"] == b["pagerank"], f"{where}: pagerank differs"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ops", type=int, default=8192)
    ap.add_argument("--batch", type=int, default=512)
    ap.add_argument("--group-commit", type=int, default=8)
    ap.add_argument("--dir", default=None)
    ap.add_argument("--kill-batch", type=int, default=None)
    ap.add_argument("--json", action="store_true")
    ap.add_argument("--_child", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)

    if args._child:
        return _child(args)

    # the parent stays off JAX until the child has died: one process per
    # chip, and the child needs it
    rng = np.random.default_rng(args.seed + 1000)
    n_batches = (args.ops + args.batch - 1) // args.batch
    kill = args.kill_batch if args.kill_batch is not None else int(
        rng.integers(n_batches // 4, max(n_batches // 4 + 1,
                                         3 * n_batches // 4)))
    workdir = args.dir or tempfile.mkdtemp(prefix="crash_smoke_")
    pathlib.Path(workdir).mkdir(parents=True, exist_ok=True)

    cmd = [sys.executable, "-m", "repro.storage.crash_smoke", "--_child",
           "--seed", str(args.seed), "--ops", str(args.ops),
           "--batch", str(args.batch),
           "--group-commit", str(args.group_commit),
           "--dir", workdir, "--kill-batch", str(kill)]
    env = dict(os.environ)
    env.setdefault("PYTHONPATH", "src")
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=1200)
    assert proc.returncode == -signal.SIGKILL, \
        f"child should die by SIGKILL, got rc={proc.returncode}\n" \
        f"{proc.stderr[-2000:]}"

    from repro.storage import recover
    store, report = recover(workdir, _mk_store)
    batches = _stream(args.seed, args.ops, args.batch)
    survived = report["last_seq"] + 1          # seqs are batch-aligned
    assert 0 <= survived <= kill + 1, (survived, kill)

    ctrl = _mk_store()
    for b in batches[:survived]:
        ctrl.apply(b)
    _assert_sig_equal(_snapshot_sig(ctrl), _snapshot_sig(store),
                      "recovered prefix")

    # restart semantics: finish the stream on the recovered store
    for b in batches[survived:]:
        store.apply(b)
    store.checkpoint()
    store.close()
    for b in batches[survived:]:
        ctrl.apply(b)
    _assert_sig_equal(_snapshot_sig(ctrl), _snapshot_sig(store),
                      "resumed stream")

    rec = dict(status="ok", seed=args.seed, ops=args.ops,
               batches=n_batches, kill_batch=kill,
               survived_batches=survived,
               lost_tail_batches=kill + 1 - survived,
               checkpoint=report["checkpoint"],
               checkpoint_kind=report["checkpoint_kind"],
               replayed=report["replayed"],
               wal_tail=str(report["wal_tail"]))
    if args.json:
        print(json.dumps(rec, indent=1))
    else:
        print(f"[OK] crash smoke: killed at batch {kill}/{n_batches}, "
              f"{survived} batches durable (ckpt {report['checkpoint']} "
              f"{report['checkpoint_kind']} + {report['replayed']} WAL "
              f"records replayed, tail={report['wal_tail']}), prefix and "
              f"resumed-stream parity bit-exact")
    return 0


if __name__ == "__main__":
    from repro.runtime import init_compile_cache
    init_compile_cache()
    sys.exit(main())
