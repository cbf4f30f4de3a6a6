"""A cell cut to a size the CPU runs in seconds, for rehearsals and tests.

Same keys, same code path; every width, length and rate scaled down. Its
numbers mean nothing about the chip. The model's own keys are cut by its
architecture module (``smoke_cut``).
"""

from __future__ import annotations

import copy

from harness.model import arch


def smoke_config(config: dict) -> dict:
    """A copy of the configuration ``config`` cut to smoke size."""
    c = copy.deepcopy(config)
    arch(c["model_type"]).smoke_cut(c)
    return c


def smoke_cell(cell: dict) -> dict:
    c = copy.deepcopy(cell)
    c["model"] = smoke_config(c["model"])
    c["engine"].update(max_batch=4, chunk=8, max_seq=256)
    mix = c["mix"]
    if mix["kind"] == "requests":
        mix["prompt"] = {"median": 32, "sigma": 0.6, "min": 4, "max": 96}
    else:
        mix.update(system_prompts=4, system_len=48, max_context=256, block=8,
                   user={"median": 12, "sigma": 0.5, "min": 4, "max": 32})
    mix["output"] = {"median": 8, "sigma": 0.5, "min": 2, "max": 24}
    load = c["load"]
    load["preroll_s"] = 1.0
    if load["mode"] == "open":
        load["rate_per_s"] = 4.0
    else:
        load["clients"] = min(load["clients"], 8)
        load["requests_per_client"] = 400
        load["sessions_per_client"] = 100
    c["check"]["sample"] = min(c["check"]["sample"], 4)
    c["trace"] = {"start_frac": 0.2, "seconds": 1.0}
    return c
