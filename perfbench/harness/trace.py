"""From a profiler trace to numbers: device busy time, kernel time, and what
the host was doing while the device sat idle.

:func:`load` reads the ``.xplane.pb`` the JAX profiler writes into a small
plain structure, which is all the reduction reads (and which a test can
hold as JSON):

- ``device``: device id -> ``[[start_ns, dur_ns, name], ...]``, the events
  of the device's ``XLA Ops`` line: one per operation that ran;
- ``spans``: ``[[name, start_ns, dur_ns, {stat: value}], ...]``, the host
  spans the benchmark writes (names starting ``bench.``).
"""

from __future__ import annotations

import bisect
import glob
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench."


def load(logdir: str) -> dict:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(f"{logdir}/**/*.xplane.pb", recursive=True))
    if not paths:
        return {"device": {}, "spans": []}
    pd = ProfileData.from_file(paths[-1])
    device: dict[str, list] = {}
    spans: list = []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                device.setdefault(m.group(1), []).extend(
                    [int(e.start_ns), int(e.duration_ns), e.name]
                    for e in line.events)
            elif not m:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append([e.name, int(e.start_ns),
                                      int(e.duration_ns), dict(e.stats)])
    for evs in device.values():
        evs.sort()
    spans.sort(key=lambda s: s[1])
    return {"device": device, "spans": spans}


def union(intervals) -> list[tuple[int, int]]:
    """Merged ``(start, end)`` intervals of ``[start, dur, ...]`` events."""
    out: list[list[int]] = []
    for ev in sorted(intervals, key=lambda e: e[0]):
        s, e = ev[0], ev[0] + ev[1]
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(merged, t0: int, t1: int) -> list[tuple[int, int]]:
    return [(max(s, t0), min(e, t1)) for s, e in merged if e > t0 and s < t1]


def busy_ns(events, t0: int, t1: int) -> int:
    return sum(e - s for s, e in clip(union(events), t0, t1))


def gaps(events, t0: int, t1: int) -> list[tuple[int, int]]:
    """Idle intervals of the device inside ``[t0, t1]``."""
    out, cur = [], t0
    for s, e in clip(union(events), t0, t1):
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if cur < t1:
        out.append((cur, t1))
    return out


def in_span(events, t0: int, t1: int) -> list:
    """The events (sorted by start) that start inside ``[t0, t1)``."""
    starts = [e[0] for e in events]
    return events[bisect.bisect_left(starts, t0):bisect.bisect_left(starts, t1)]


def leaves(events) -> list:
    """The events that contain no other: an op such as the layer scan's
    ``while`` spans the ops of its body, which the line also holds."""
    return [e for e, nxt in zip(events, events[1:] + [None])
            if nxt is None or nxt[0] >= e[0] + e[1]]


HLO_OP = re.compile(r"^%([\w.\-]+) = (\S+)")
TARGET = re.compile(r'custom_call_target="([^"]+)"|kind=(k\w+)')


def short_name(name: str) -> str:
    """An HLO op's event name (the instruction's whole text) cut to its
    name, result type and target or fusion kind."""
    m = HLO_OP.match(name)
    if not m:
        return name[:120]
    t = TARGET.search(name)
    kind = (t.group(1) or t.group(2)) if t else ""
    return f"{m.group(1)} {m.group(2)[:60]} {kind}".strip()


def top_ops(events, t0: int, t1: int, n: int = 10) -> list[list]:
    """The ``n`` operations (innermost ones) that took the most device
    time."""
    tot: dict[str, int] = defaultdict(int)
    for s, d, name in leaves(events):
        if t0 <= s < t1:
            tot[short_name(name)] += d
    return [[k, v / 1e9] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def span_at(spans, t: int) -> str:
    """Name of the innermost benchmark span open at ``t``, else ``idle``
    (the host was outside every step)."""
    best = None
    for name, s, d, _ in spans:
        if s <= t < s + d and (best is None or d < best[1]):
            best = (name, d)
    return best[0] if best else "idle"


def idle_by_span(events, spans, t0: int, t1: int, n: int = 10
                 ) -> list[list]:
    """Idle device time inside ``[t0, t1]``, summed by the host span open
    at each gap's midpoint, largest first."""
    starts = [s[1] for s in spans]
    tot: dict[str, int] = defaultdict(int)
    for a, b in gaps(events, t0, t1):
        mid = (a + b) // 2
        # spans open at ``mid`` started before it; look back a little way
        i = bisect.bisect_right(starts, mid)
        near = spans[max(0, i - 64):i]
        tot[span_at(near, mid)] += b - a
    return [[k, v / 1e9] for k, v in
            sorted(tot.items(), key=lambda kv: -kv[1])[:n]]


def step_spans(spans) -> list[dict]:
    """The benchmark's step spans, each with its replica and step index."""
    return [{"start": s, "end": s + d, "replica": int(st.get("replica", 0)),
             "step": int(st["step"])}
            for name, s, d, st in spans if name == "bench.step"]
