#!/usr/bin/env python3
"""Readings that the limits of ``compare.py`` are set from, on the chip.

    python3 benchmarks/chip/control.py --workload u19.serve \\
        --seconds 10 --seeds 101 102 103 ...

For each seed, in one process (so programs compile once): run the cell
with a short window, then compare both what the program produced (the
lower reading of each number) and what the control produces (the
reference one precision lower in the program's place: the upper
reading). Prints one JSON line per seed and, last, each number's largest
program reading and smallest control reading. The benchmark's own runs
never run the control. Exits non-zero off the chip, like ``run.py``.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

from run import CHECKOUT  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)

    import harness
    import kernel_bytes
    cell = harness.resolve_cell(args.workload)
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CHECKOUT / ".jax_cache")
    sys.path.insert(0, str(CHECKOUT / "src"))
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell.chips:
        print(f"control.py: needs {cell.chips} TPU chips, found "
              f"{len(devs)} {devs[0].platform!r}", file=sys.stderr)
        return 2
    peaks = kernel_bytes.peaks(devs[0].device_kind)
    from repro.runtime import init_compile_cache
    init_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    lower, upper = {}, {}
    t = T0
    for seed in args.seeds:
        out = harness.run_cell(cell, seed, args.seconds, False, t, peaks,
                               with_control=True)
        t = time.perf_counter()
        for k, c in out["checks"].items():
            lower[k] = max(lower.get(k, c["value"]), c["value"])
        for k, c in out["control_checks"].items():
            upper[k] = min(upper.get(k, c["value"]), c["value"])
        print(json.dumps({"seed": seed, "correct": out["correct"],
                          "control_correct": out["control_correct"],
                          "metrics": out["metrics"],
                          "program": {k: c["value"]
                                      for k, c in out["checks"].items()},
                          "control": {k: c["value"] for k, c in
                                      out["control_checks"].items()}}),
              flush=True)
    print(json.dumps({"workload": cell.name, "seeds": len(args.seeds),
                      "program_max": lower, "control_min": upper}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
