#!/usr/bin/env python3
"""Run one benchmark cell on the chip and print its result line.

    python3 benchmarks/chip/run.py --workload u19.insert --seed 7 \\
        --seconds 45 --trace 0

Run from the root of a checkout. Set-up (the seeded graph, the store,
loading or compiling its programs, the cell's preload and warm-up rounds)
is timed as ``setup_s``; then the cell's traffic runs for ``--seconds``;
then what the window produced is compared with the plain reference.
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` traces
the window with the JAX profiler and reports its per-layer metrics.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared with its limit).
The last lines of standard error repeat the checks. The run exits non-zero
and prints no result when JAX finds no TPU, fewer chips than the cell
asks for, or a chip that ``peaks.json`` does not know.

JAX's persistent compilation cache lives at ``<checkout>/.jax_cache``.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import harness
    import kernel_bytes
    cell = harness.resolve_cell(args.workload)
    # the cache directory is fixed inside the checkout: the path is part of
    # the cache key, and the program takes the directory it is given here
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CHECKOUT / ".jax_cache")
    sys.path.insert(0, str(CHECKOUT / "src"))
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"run.py: no TPU (JAX platform {devs[0].platform!r})",
              file=sys.stderr)
        return 2
    if len(devs) < cell.chips:
        print(f"run.py: {cell.name} asks {cell.chips} chips, "
              f"{len(devs)} found", file=sys.stderr)
        return 2
    peaks = kernel_bytes.peaks(devs[0].device_kind)
    from repro.runtime import init_compile_cache
    init_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    out = harness.run_cell(cell, args.seed, args.seconds, bool(args.trace),
                           T0, peaks)
    for k, c in out["checks"].items():
        print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
