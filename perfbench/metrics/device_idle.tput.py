"""Device: share of the traced window in which no operation ran, averaged
over the cell's chips. Moves ``output_tokens_per_s``."""

from harness import layers


def read(run):
    v = layers.idle_pct(run)
    return None if v is None else (v, "%")
