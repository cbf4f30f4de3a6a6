"""Scheduler: share of the window's steps that run chunk-wide because some
row is mid-prompt, from the step record. Moves ``itl_p95_ms``."""


def read(run):
    chunk = run.cell["engine"]["chunk"]
    steps = [s for s in run.steps if run.w0 <= s.t0 and s.t1 <= run.w1]
    if not steps:
        return None
    return 100.0 * sum(1 for s in steps if s.C == chunk) / len(steps), "%"
