"""Engine step loop: mean wall time of the window's ``bench.step`` spans
(host clock, admission to the host's read of the sampled tokens). Moves
``itl_p50_ms``."""


def read(run):
    steps = [s for s in run.steps if run.w0 <= s.t0 and s.t1 <= run.w1]
    if not steps:
        return None
    return 1e3 * sum(s.t1 - s.t0 for s in steps) / len(steps), "ms"
