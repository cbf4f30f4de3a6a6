"""Scheduler: share of the prompt tokens of requests sent in the window
and admitted that the prefix cache served, from
``RequestMetrics.prefix_hit_tokens``. Moves ``output_tokens_per_s``."""


def read(run):
    admitted = [r for r in run.records if run.w0 <= r.due < run.w1
                and r.req.metrics.admit_t > 0]
    prompt = sum(len(r.req.prompt) for r in admitted)
    if not prompt:
        return None
    hit = sum(r.req.metrics.prefix_hit_tokens for r in admitted)
    return 100.0 * hit / prompt, "%"
