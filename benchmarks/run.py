"""Benchmark driver — one function per paper table/figure.

  PYTHONPATH=src python -m benchmarks.run [--scale 1.0] [--only fig8,table5]

Prints CSV rows (``table,...,value`` per line). Roofline/dry-run artifacts
are separate (benchmarks.roofline, repro.launch.dryrun) since they need the
512-placeholder-device environment.
"""
from __future__ import annotations

import argparse
import sys
import time


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=0.25,
                    help="dataset scale factor (1.0 = Table-4-mini sizes)")
    ap.add_argument("--only", default=None,
                    help="comma-separated subset, e.g. fig8,table5")
    args = ap.parse_args(argv)

    from . import (fig8_ops, fig9_mixed, fig10_analytics, fig11_concurrent,
                   fig12_sort_case, fig13_workloads, table2_radix_structures,
                   table5_sort_vs_art, table6_ablation, table7_batch)

    # fig11 first: its 4-shard worker process must start before this
    # process touches JAX (a parent holding the chip would starve it)
    suites = {
        "fig11": fig11_concurrent.run,
        "table2": table2_radix_structures.run,
        "fig8": fig8_ops.run,
        "fig9": fig9_mixed.run,
        "fig10": fig10_analytics.run,
        "fig12": fig12_sort_case.run,
        "fig13": fig13_workloads.run,
        "table5": table5_sort_vs_art.run,
        "table6": table6_ablation.run,
        "table7": table7_batch.run,
    }
    only = set(args.only.split(",")) if args.only else set(suites)
    failed = []
    for name, fn in suites.items():
        if name not in only:
            continue
        t0 = time.time()
        print(f"# ==== {name} (scale={args.scale}) ====")
        try:
            fn(scale=args.scale)
        except Exception:  # noqa: BLE001
            import traceback
            traceback.print_exc()
            failed.append(name)
        print(f"# {name} done in {time.time() - t0:.1f}s")
    if failed:
        print(f"# FAILED: {failed}")
        sys.exit(1)


if __name__ == "__main__":
    from repro.runtime import init_compile_cache
    init_compile_cache()
    main()
