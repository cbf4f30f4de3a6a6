"""Find a cell's files by name.

A cell (``workloads/<cell>.json``) names a model configuration
(``configs/<config>.json``), a traffic mix (``traffic/<mix>.json``) and the
per-layer metrics it reports (``metrics/<metric>.py``). Nothing here knows a
cell by name: a later cell is new data files and nothing else.
"""

from __future__ import annotations

import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]     # perfbench/
REPO = BENCH.parent                             # the checkout's root


def load(kind: str, name: str) -> dict:
    path = BENCH / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} named {name!r} ({path})")
    return json.loads(path.read_text())


def load_cell(name: str) -> dict:
    """The cell's own file, with its configuration and mix loaded beside
    it under ``model`` and ``mix``."""
    cell = load("workloads", name)
    cell["name"] = name
    cell["model"] = load("configs", cell["config"])
    cell["mix"] = load("traffic", cell["traffic"])
    return cell


def metric_path(name: str) -> Path:
    return BENCH / "metrics" / f"{name}.py"
