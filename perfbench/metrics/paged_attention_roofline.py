"""Kernel: the paged-attention walk's share of its roofline. The least time
is the larger of its FLOPs over the bf16 peak and its bytes over HBM
bandwidth, both counted from the step record (QK^T and PV over the visible
positions; live K and V, q and the output); the time is the device time of
the walk's trace events in the same steps. Moves ``output_tokens_per_s``.

The program names none of its Pallas calls, so the walk is known by what
it is: among a step's Pallas calls (``tpu_custom_call``), the one that takes
the page table (``s32[B,max_blocks]``, the shape the step was given) and
aliases no operand to its output, as the paged writes alias the pool they
update. A step has to run as many such calls, of as many distinct
programs, as the model's architecture module says (``walk_calls``: one
program once per layer for every architecture so far). Any other Pallas
call that aliases nothing, or a walk that aliases, or another count of
calls or programs, raises: the reader would otherwise time another kernel,
or none, without a word. A step with no Pallas call (kernels other
than Pallas) has nothing to read."""

import re

from harness import counts, layers

PALLAS = 'custom_call_target="tpu_custom_call"'
ALIASES = "output_to_operand_aliasing"
HLO_NAME = re.compile(r"^%([\w.\-]+) = ")


def _name(text: str) -> str:
    m = HLO_NAME.match(text)
    return m.group(1) if m else text[:80]


def walk_ns(events, step, model) -> int:
    """Device time of the paged walk among one step's device events."""
    pallas = [e for e in events if PALLAS in e[2]]
    if not pallas:
        return 0
    table = "s32[%d,%d]" % tuple(step.pages)
    walk = [e for e in pallas if ALIASES not in e[2] and table in e[2]]
    stray = [e for e in pallas if ALIASES not in e[2] and table not in e[2]]
    names = sorted({_name(e[2]) for e in walk})
    calls, programs = model.walk_calls()
    if stray or len(names) != programs or len(walk) != calls:
        raise ValueError(
            f"step {step.step}: the paged walk is not {calls} Pallas calls "
            f"of {programs} program(s) that take the {table} page table and "
            f"alias nothing: {len(walk)} such calls ({names}), and "
            f"{len(stray)} other unaliased Pallas calls "
            f"({sorted({_name(e[2]) for e in stray})})")
    return sum(d for _, d, _ in walk)


def read(run):
    v = layers.kernel_roofline(run, walk_ns, counts.paged_attention_cost)
    return None if v is None else (v, "%")
