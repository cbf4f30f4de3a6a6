"""Per-layer quantities that several metric readers share: the traced
window, device busy time, a kernel's roofline share, and the breakdown.

The traced window runs from the start of the first to the end of the last
``bench.step`` span in the trace, on the trace's clock; every device event
and span is read against it.
"""

from __future__ import annotations

from harness import counts
from harness import trace as tr


def window_ns(run) -> tuple[int, int] | None:
    steps = tr.step_spans(run.trace["spans"]) if run.trace else []
    if not steps:
        return None
    return min(s["start"] for s in steps), max(s["end"] for s in steps)


def _device_events(run) -> list[list]:
    return [run.trace["device"].get(d, []) for d in run.devices]


def device_busy(run) -> tuple[float, float]:
    """(busy seconds averaged over the cell's devices, window seconds);
    (0, 0) when the trace holds no device operation."""
    w = window_ns(run)
    evs = _device_events(run) if w else []
    if not w or not any(evs):
        return 0.0, 0.0
    busy = sum(tr.busy_ns(e, *w) for e in evs) / len(evs)
    return busy / 1e9, (w[1] - w[0]) / 1e9


def idle_pct(run) -> float | None:
    busy, window = device_busy(run)
    if not window:
        return None
    return 100.0 * (1.0 - busy / window)


def kernel_roofline(run, kernel_ns, cost) -> float | None:
    """Roofline share (%) of one kernel: over every step traced whole, the
    least time the chip needs for the work the step record says
    (``cost(model, rows)`` gives FLOPs and bytes), over the kernel's device
    time in those steps. ``kernel_ns(events, step, model)`` picks that time
    out of the device events that start inside the step's span (0 where
    the step ran no such kernel)."""
    if not run.trace or run.peaks is None:
        return None
    by_step = {(s.replica, s.step): s for s in run.steps}
    flops = nbytes = dev_ns = 0
    for sp in tr.step_spans(run.trace["spans"]):
        rec = by_step.get((sp["replica"], sp["step"]))
        if rec is None:
            continue
        evs = run.trace["device"].get(run.devices[sp["replica"]], [])
        ns = kernel_ns(tr.in_span(evs, sp["start"], sp["end"]), rec,
                       run.model)
        if not ns:
            continue
        f, b = cost(run.model, rec.rows)
        flops, nbytes, dev_ns = flops + f, nbytes + b, dev_ns + ns
    if not dev_ns:
        return None
    least = max(flops / run.peaks.bf16_flops,
                nbytes / run.peaks.hbm_bytes_per_s)
    return 100.0 * least / (dev_ns / 1e9)


def step_mfu(run) -> float | None:
    """Model FLOPs of the steps inside the window over the window's seconds
    times the chips' bf16 peak, in %."""
    if run.peaks is None or not run.steps:
        return None
    flops = sum(counts.step_flops(run.model, s.rows) for s in run.steps
                if run.w0 <= s.t0 and s.t1 <= run.w1)
    return 100.0 * flops / (run.seconds * run.chips * run.peaks.bf16_flops)


def breakdown(run) -> dict:
    """Device operations that took the most time, and idle device time by
    the host span open in each gap, summed over the cell's devices."""
    w = window_ns(run)
    ops: dict[str, float] = {}
    idle: dict[str, float] = {}
    for rid, evs in enumerate(_device_events(run)):
        spans = [sp for sp in run.trace["spans"]
                 if int(sp[3].get("replica", 0)) == rid]
        for name, s in tr.top_ops(evs, *w, n=10):
            ops[name] = ops.get(name, 0.0) + s
        for name, s in tr.idle_by_span(evs, spans, *w, n=10):
            idle[name] = idle.get(name, 0.0) + s
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:10]
    return {"device_ops": [[k, v] for k, v in top],
            "idle_gaps": [[k, v] for k, v in gaps]}
