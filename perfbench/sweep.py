#!/usr/bin/env python3
"""Find the knee of an open-loop cell: the highest arrival rate the system
sustains without a growing backlog.

    python3 perfbench/sweep.py --workload granite-moe.chat \\
        --rates 2,3,4,5,6,8 --seconds 20 --seed 1

One process, one set-up: the cell's engine is built and warmed once, then
each rate in turn sends the cell's traffic open loop for ``--seconds``,
after which its requests are dropped before the next rate. Per rate it
prints one JSON line: the offered rate, the rate of requests completed in
the phase, the requests in flight and still queued (not yet admitted)
half-way and at the end, and the phase's TTFT and inter-token tails. Give
each phase a few times a request's lifetime. The knee is the highest rate
whose queue does not grow over the phase; the cell's ``rate_per_s`` is
set at about four fifths of it.
Run it on the chip; it exits 2 without a TPU.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)

    import jax
    import numpy as np

    from harness import drive, spec
    from harness.model import for_cell, make_params
    from repro.launch.cache import enable_compile_cache
    from repro.serving.frontend import EngineWorker

    cell = spec.load_cell(args.workload)
    if jax.devices()[0].platform != "tpu":
        print("sweep.py: needs a TPU", file=sys.stderr)
        return 2
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    drive.setup_context()
    m = for_cell(cell)
    engines, router = drive.build_engines(cell, m, make_params(m, args.seed))
    for e in engines:
        drive.warm(e, drive.warm_shapes(cell))
    print(json.dumps({"setup_s": time.perf_counter() - T_START}), flush=True)
    for rate in (float(r) for r in args.rates.split(",")):
        c = copy.deepcopy(cell)
        c["load"]["rate_per_s"] = rate
        # fresh step threads over the same engines; stopping them without a
        # drain frees every slot for the next rate
        workers = [EngineWorker(e) for e in engines]
        for w in workers:
            w.start()
        client = drive.Client(c, m, args.seed, workers, router)
        client.plan(args.seconds)
        t0 = time.perf_counter()
        try:
            client.start(t0)
            client.poll(t0 + args.seconds / 2)
            queued_mid = sum(1 for r in client.records
                             if r.req.metrics.admit_t == 0.0)
            client.poll(t0 + args.seconds)
            t1 = time.perf_counter()
            recs = list(client.records)
            times = {id(r): list(r.times) for r in recs}
            done = [r for r in recs if r.done_t <= t1]
            queued = [r for r in recs if r.req.metrics.admit_t == 0.0]
        finally:
            for w in workers:
                w.stop(drain=False)
        ttft = [min(times[id(r)][0], t1) - r.due if times[id(r)]
                else t1 - r.due for r in recs]
        itl = [b - a for r in recs
               for a, b in zip(times[id(r)], times[id(r)][1:])]
        print(json.dumps({
            "rate_per_s": rate, "sent": len(recs),
            "completed_per_s": len(done) / (t1 - t0),
            "in_flight": len(recs) - len(done) - len(queued),
            "queued_mid": queued_mid, "queued": len(queued),
            "tokens_per_s": sum(len(v) for v in times.values()) / (t1 - t0),
            "ttft_p95_s": float(np.percentile(ttft, 95)),
            "itl_p50_ms": 1e3 * float(np.percentile(itl, 50)),
            "itl_p95_ms": 1e3 * float(np.percentile(itl, 95))}),
            flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
