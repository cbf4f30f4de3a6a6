"""Operations and bytes a step requires, from the step record alone.

A step's rows are ``(pos, length, emits)``: the row's cached context before
the step, how many of its tokens the step absorbs, and whether the step
samples a token for it. Only active rows are counted, and only their valid
tokens: what the work requires, whatever grid, block size or padding the
program uses to do it.

The model's architecture module gives each layer's matmul FLOPs per token
(``token_flops``) and the (query, key) pairs and keys it attends over
(``attended``); the sums over layers are here.
"""

from __future__ import annotations

Row = tuple[int, int, bool]


def visible_pairs(rows: list[Row], window: int | None = None) -> int:
    """(query, key) pairs of causal attention over each row's context:
    query ``i`` of a row at ``pos`` sees ``pos + i + 1`` keys, or at most
    ``window``."""
    if window is None:
        return sum(n * pos + n * (n + 1) // 2 for pos, n, _ in rows)
    return sum(min(pos + i + 1, window) for pos, n, _ in rows
               for i in range(n))


def context_keys(rows: list[Row], window: int | None = None) -> int:
    """Keys the rows' queries see between them: the whole context, or the
    last ``window`` keys before the first query and every later one."""
    if window is None:
        return sum(pos + n for pos, n, _ in rows)
    return sum(min(pos + n, window + n - 1) for pos, n, _ in rows)


def step_flops(m, rows: list[Row]) -> int:
    """Model FLOPs a step requires: every layer's matmuls for the valid
    tokens, attention over the context each layer sees, and the head for
    the rows that emit a token."""
    tokens = sum(n for _, n, _ in rows)
    layers = sum(tokens * m.token_flops(i)
                 + 4 * m.heads * m.head_dim * m.attended(i, rows)[0]
                 for i in range(m.layers))
    head = 2 * m.d * m.vocab * sum(1 for *_, e in rows if e)
    return layers + head


def paged_attention_cost(m, rows: list[Row], itemsize: int = 2
                         ) -> tuple[int, int]:
    """(FLOPs, bytes) of the paged walk over all layers of one step: QK^T
    and PV over the pairs each layer attends over; the keys it sees read
    once as K and V, q read and the output written once."""
    hd = m.head_dim
    qo = sum(n for _, n, _ in rows) * 2 * m.heads * hd
    flops = nbytes = 0
    for i in range(m.layers):
        pairs, keys = m.attended(i, rows)
        flops += 4 * m.heads * hd * pairs
        nbytes += (keys * 2 * m.kv_heads * hd + qo) * itemsize
    return flops, nbytes
