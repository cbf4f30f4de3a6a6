"""Operations and bytes a step requires, from the step record alone.

A step's rows are ``(pos, length, emits)``: the row's cached context before
the step, how many of its tokens the step absorbs, and whether the step
samples a token for it. Only active rows are counted, and only their valid
tokens: what the work requires, whatever grid, block size or padding the
program uses to do it.
"""

from __future__ import annotations

from harness.model import Model

Row = tuple[int, int, bool]


def token_flops(m: Model) -> int:
    """Matmul FLOPs of one token through one layer (active experts only)."""
    d, hd = m.d, m.head_dim
    attn = 2 * d * (m.heads + 2 * m.kv_heads) * hd + 2 * m.heads * hd * d
    if m.family == "moe":
        ffn = 2 * d * m.experts + m.top_k * 3 * 2 * d * m.ffn
    else:
        ffn = 3 * 2 * d * m.ffn
    return attn + ffn


def visible_pairs(rows: list[Row]) -> int:
    """(query, key) pairs of causal attention over each row's context:
    query ``i`` of a row at ``pos`` sees ``pos + i + 1`` keys."""
    return sum(n * pos + n * (n + 1) // 2 for pos, n, _ in rows)


def step_flops(m: Model, rows: list[Row]) -> int:
    """Model FLOPs a step requires: every layer's matmuls for the valid
    tokens, attention over each row's real context, and the head for the
    rows that emit a token."""
    tokens = sum(n for _, n, _ in rows)
    per_layer = tokens * token_flops(m) + 4 * m.heads * m.head_dim \
        * visible_pairs(rows)
    head = 2 * m.d * m.vocab * sum(1 for *_, e in rows if e)
    return m.layers * per_layer + head


def paged_attention_cost(m: Model, rows: list[Row], itemsize: int = 2
                         ) -> tuple[int, int]:
    """(FLOPs, bytes) of the paged walk over all layers of one step: QK^T
    and PV over the visible positions; the live context's K and V read once,
    q read and the output written once."""
    hd = m.head_dim
    flops = 4 * m.heads * hd * visible_pairs(rows)
    kv = sum(pos + n for pos, n, _ in rows) * 2 * m.kv_heads * hd
    qo = sum(n for _, n, _ in rows) * 2 * m.heads * hd
    return m.layers * flops, m.layers * (kv + qo) * itemsize
