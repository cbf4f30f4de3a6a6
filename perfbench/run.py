#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip, and print its result.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

The cell is ``perfbench/workloads/<cell>.json``. The run makes the weights
from ``--seed``, warms up the step shapes the cell's traffic uses (set-up,
reported as ``setup_s``), drives the traffic through the served path for
``--seconds``, then checks the served tokens against the plain reference.
``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` repeats
the run with the profiler on for part of the window and reports the cell's
per-layer metrics instead.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, and with ``--trace 1``
``breakdown``; ``checks`` last); the last lines of standard error give each
number the correctness check compared, beside its limit. Without a TPU, or
with fewer chips than the cell needs, it prints no result and exits 2.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from harness import spec
    cell = spec.load_cell(args.workload)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell["chips"]:
        print(f"run.py: cell {args.workload} needs {cell['chips']} TPU "
              f"chip(s); JAX found {len(devices)} {devices[0].platform} "
              f"device(s)", file=sys.stderr)
        return 2

    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    # cache every program, however quick to compile, so that a run after
    # the first compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    from harness import drive
    result = drive.run(cell, args.seed, args.seconds, bool(args.trace),
                       t_start=T_START)
    emit(result)
    return 0


def emit(result: dict) -> None:
    """Each compared number beside its limit, as the last lines of
    standard error; then the result as the last line of standard output."""
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(f"check correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    sys.exit(main())
