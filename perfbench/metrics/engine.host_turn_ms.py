"""Engine step loop: the host's turn between two steps, the mean over each
engine's consecutive traced steps N and N + 1 of the time from the end of
step N's ``serve.readback`` span to the end of step N + 1's
``serve.dispatch``: the synchronous loop leaves the chip with nothing
queued for that long. Moves ``output_tokens_per_s``.

Each phase span belongs to the ``serve.step`` span of its replica that
holds it; the step's ``step`` stat numbers the engine's steps. A trace
without ``serve.*`` spans has nothing to read."""

import bisect
from collections import defaultdict


def step_phases(spans) -> dict:
    """``(replica, step) -> {phase: end_ns}`` of the phase spans inside
    each ``serve.step`` span."""
    steps = defaultdict(list)
    for name, s, d, st in spans:
        if name == "serve.step":
            steps[int(st.get("replica", 0))].append((s, s + d, int(st["step"])))
    for v in steps.values():
        v.sort()
    out: dict = defaultdict(dict)
    for name, s, d, st in spans:
        if not name.startswith("serve.") or name == "serve.step":
            continue
        mine = steps.get(int(st.get("replica", 0)), [])
        i = bisect.bisect_right(mine, (s, float("inf"))) - 1
        if i >= 0 and s + d <= mine[i][1]:
            out[(int(st.get("replica", 0)), mine[i][2])][name] = s + d
    return out


def read(run):
    phases = step_phases(run.trace["spans"]) if run.trace else {}
    turns = [nxt["serve.dispatch"] - cur["serve.readback"]
             for (rid, i), cur in phases.items()
             if (nxt := phases.get((rid, i + 1)))
             and "serve.readback" in cur and "serve.dispatch" in nxt]
    if not turns:
        return None
    return sum(turns) / len(turns) / 1e6, "ms"
