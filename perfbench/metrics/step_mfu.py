"""Model step: model FLOPs the window's steps require (matmuls of the valid
tokens, attention over each row's real context, the head for rows that
emit), over the window times the chips' bf16 peak. Moves
``output_tokens_per_s``."""

from harness import layers


def read(run):
    v = layers.step_mfu(run)
    return None if v is None else (v, "%")
