"""Router: share of keyed routing decisions that landed on a replica which
already held the prompt's prefix, from ``Router.stats()``. Moves
``output_tokens_per_s``."""


def read(run):
    v = (run.router or {}).get("affinity_hit_rate")
    return None if v is None else (100.0 * v, "%")
