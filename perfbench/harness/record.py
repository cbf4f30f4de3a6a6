"""The step record and the benchmark's host spans (traced runs only).

Installed from the benchmark's own files by instance-attribute shadowing,
as ``serving/faults.py`` does: the engine's ``step``, ``_admit`` and jitted
``_step`` are wrapped on the instance, the class is untouched, and runs
without ``--trace 1`` never see the wrappers. Spans are
``jax.profiler.TraceAnnotation`` s, so they land in the profiler's trace on
the device's clock:

- ``bench.step`` (stats ``step``, ``replica``): one engine step, whole;
- ``bench.admit``: admission into free slots, at the top of the step;
- ``bench.dispatch``: building the step's arguments on the device and
  dispatching the jitted step (it returns before the device is done).

Per step the record keeps B, C and each active row's ``(pos, length,
emits)``, read from what the step dispatches: B and C are the token array's
shape, ``pos`` and ``length`` the arrays the jitted step is given, and a row
emits when the dispatch leaves none of its prompt pending. The page table's
shape goes with them, for the roofline reader to know the walk by.
"""

from __future__ import annotations

import dataclasses
import itertools
import time

import numpy as np
from jax.profiler import TraceAnnotation


@dataclasses.dataclass
class Step:
    replica: int
    step: int
    t0: float                   # perf_counter around the whole step
    t1: float
    B: int
    C: int
    rows: list                  # [(pos, length, emits)] of active rows
    pages: tuple                # shape of the page table, (B, max_blocks)


class StepRecorder:
    def __init__(self, engines):
        self.steps: list[Step] = []
        for rid, e in enumerate(engines):
            if not e.paged:
                raise ValueError("the step record reads a paged engine's step")
            self._install(rid, e)

    def _install(self, rid: int, e) -> None:
        orig_step, orig_admit, orig_jit = e.step, e._admit, e._step
        counter = itertools.count()
        seen: dict = {}

        def admit(now):
            with TraceAnnotation("bench.admit", replica=rid):
                orig_admit(now)

        def step():
            i = next(counter)
            t0 = time.perf_counter()
            with TraceAnnotation("bench.step", step=i, replica=rid):
                n = orig_step()
            t1 = time.perf_counter()
            if "rows" in seen:
                self.steps.append(Step(rid, i, t0, t1, *seen.pop("rows")))
            return n

        def dispatch(params, tokens, state, pages, pos, length, *rest, **kw):
            B, C = tokens.shape
            p, n = np.asarray(pos), np.asarray(length)
            sched = e.scheduler
            seen["rows"] = (B, C, [
                (int(p[s]), int(n[s]), not sched.pending_prompt[s])
                for s, r in enumerate(sched.active) if r is not None],
                tuple(pages.shape))
            with TraceAnnotation("bench.dispatch", replica=rid):
                return orig_jit(params, tokens, state, pages, pos, length,
                                *rest, **kw)

        e.step, e._admit, e._step = step, admit, dispatch
