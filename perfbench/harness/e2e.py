"""End-to-end metrics, on the host's clock, from what the client saw.

- ``output_tokens_per_s``: every token delivered to a client inside the
  window, over the window's seconds.
- ``ttft_p95_s``: 95th percentile over the requests due inside the window of
  first token minus scheduled send time; a request that failed, or had no
  token by the window's end, counts as the window's end minus its due time.
- ``itl_p50_ms`` / ``itl_p95_ms``: median and 95th percentile of every gap
  between two consecutive tokens of one request, both inside the window.
"""

from __future__ import annotations

import numpy as np


def compute(run) -> dict[str, tuple[float, str]]:
    w0, w1 = run.w0, run.w1
    tokens = sum(1 for r in run.records for t in r.times if w0 <= t < w1)
    ttft = []
    for r in run.records:
        if not w0 <= r.due < w1:
            continue
        first = r.times[0] if r.times and r.error is None else w1
        ttft.append(min(first, w1) - r.due)
    itl = [b - a for r in run.records
           for a, b in zip(r.times, r.times[1:]) if w0 <= a and b < w1]
    out = {"output_tokens_per_s": (tokens / run.seconds, "tokens/s")}
    if ttft:
        out["ttft_p95_s"] = (float(np.percentile(ttft, 95)), "s")
    if itl:
        out["itl_p50_ms"] = (1e3 * float(np.percentile(itl, 50)), "ms")
        out["itl_p95_ms"] = (1e3 * float(np.percentile(itl, 95)), "ms")
    return out
