"""Device: share of the traced window in which no operation ran on the
chip, 1 - (union of device-op intervals) / window. Moves ``itl_p50_ms``."""

from harness import layers


def read(run):
    v = layers.idle_pct(run)
    return None if v is None else (v, "%")
