#!/usr/bin/env python3
"""Record the profiler trace of one round of a cell, for the CPU tests'
trace fixtures (``fixtures/<name>.xplane.pb.gz``).

    python3 benchmarks/chip/record_trace.py --workload u19.insert \\
        --seed 7 --out u19_insert.xplane.pb.gz

Runs ``run.py --trace 1 --seconds 0``: set-up as in any run, then a traced
window of one round of the cell's loop (one apply of the insert mix; one
apply, capture and read of the serving mix's first window round, which
runs no analytics). The window's trace is written gzipped to ``--out``
before the harness reduces it; the result line is ``run.py``'s.
"""
import argparse
import gzip
import pathlib
import sys

import run
import scopetrace
import tracereduce


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    out = pathlib.Path(args.out)

    def keep(path):
        raw = scopetrace.raw_bytes(path)
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_bytes(gzip.compress(raw))
        return scopetrace.load(path)

    tracereduce.load = keep
    return run.main(["--workload", args.workload, "--seed", str(args.seed),
                     "--seconds", "0", "--trace", "1"])


if __name__ == "__main__":
    sys.exit(main())
