"""Shared benchmark utilities."""

from __future__ import annotations

import json
import platform
import time
from collections.abc import Callable

import jax

from repro.launch.cache import enable_compile_cache

enable_compile_cache()


def time_fn(fn: Callable, *args, warmup: int = 2, iters: int = 5,
            **kwargs) -> float:
    """Median wall-clock microseconds per call (after jit warmup)."""
    for _ in range(warmup):
        out = fn(*args, **kwargs)
        jax.block_until_ready(out)
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        out = fn(*args, **kwargs)
        jax.block_until_ready(out)
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2] * 1e6


_RESULTS: list[dict] = []


def emit(name: str, us: float, derived: str = "") -> None:
    """Print one CSV result line and collect it for :func:`write_json`."""
    print(f"{name},{us:.1f},{derived}", flush=True)
    _RESULTS.append({"name": name, "us": round(us, 1), "derived": derived})


def write_json(path: str) -> None:
    """Dump every emitted result (plus run metadata) as a JSON artifact —
    CI uploads this per run so regressions are diffable across commits."""
    doc = {
        "results": _RESULTS,
        "meta": {
            "backend": jax.default_backend(),
            "device_count": jax.device_count(),
            "python": platform.python_version(),
            "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S%z"),
        },
    }
    with open(path, "w") as f:
        json.dump(doc, f, indent=2)
    print(f"wrote {len(_RESULTS)} results to {path}", flush=True)
