#!/usr/bin/env python3
"""Read the two ends a cell's correctness limit is set between.

    python3 perfbench/control.py --workload <cell> --seeds 11,12,13 \\
        --seconds 20

For each seed, in one process, it runs the cell as ``run.py`` does (at the
cell's own sizes and load, for a window of ``--seconds``) and then judges,
over the same sample of served requests and by the same comparison and
limit, the program's served tokens (``correct``) and the tokens that the
reference computed in float8 puts first (``control_correct``: the control,
one precision below the bfloat16 the configurations state, which has to
come out false). The benchmark's own runs do not run the control. One JSON
line per seed, with both widest logit gaps; it exits 2 without a TPU.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)

    import jax

    from harness import drive, spec
    from repro.launch.cache import enable_compile_cache
    cell = spec.load_cell(args.workload)
    if jax.devices()[0].platform != "tpu":
        print("control.py: needs a TPU", file=sys.stderr)
        return 2
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    for seed in (int(s) for s in args.seeds.split(",")):
        res = drive.run(cell, seed, args.seconds, False,
                        t_start=time.perf_counter(), control=True)
        c, ctl = res["checks"], res.get("control", {})
        print(json.dumps({
            "seed": seed, "correct": res["correct"],
            "control_correct": ctl.get("correct"),
            "served_gap": c["max_logit_gap"]["value"],
            "control_gap": ctl.get("max_logit_gap", {}).get("value"),
            "limit": c["max_logit_gap"]["limit"],
            "tokens": c.get("tokens_checked", {}).get("value"),
            "metrics": res["metrics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
