"""Scheduler: 95th percentile of the queue wait of the requests sent in the
window that were admitted, from ``RequestMetrics.queued_s`` (the sum of
each stint's wait between submission and admission). Moves
``ttft_p95_s``."""

import math

import numpy as np


def read(run):
    waits = [r.req.metrics.queued_s for r in run.records
             if run.w0 <= r.due < run.w1
             and not math.isnan(r.req.metrics.queued_s)]
    if not waits:
        return None
    return float(np.percentile(waits, 95)), "s"
