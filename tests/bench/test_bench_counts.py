"""Peaks table and the FLOP and byte counts, against hand-worked shapes."""

import json

import pytest

from harness import counts, peaks
from harness.model import param_count, parse
from harness.spec import BENCH


def model(name):
    return parse(
        name, json.loads((BENCH / "configs" / f"{name}.json").read_text()))


def test_peaks_known_and_unknown():
    p = peaks.peaks("TPU v5 lite")
    assert p.bf16_flops == 197e12 and p.hbm_bytes_per_s == 819e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v4")


def test_param_counts_match_the_published_sizes():
    # granite-moe-1b-a400m: 1.33 B; mistral-nemo stage: 10 x 272.6 M + 2 x 671 M
    assert param_count(model("granite-moe-1b-a400m")) == 1_334_628_352
    m = model("mistral-nemo-12b-pp4")
    # (each layer's 272.64 M holds its two norm gains; + the final norm)
    assert param_count(m) == 10 * 272_640_000 + 2 * 671_088_640 + 5120


def test_token_flops_by_hand():
    m = model("mistral-nemo-12b-pp4")
    # q 5120x4096, k and v 5120x1024, o 4096x5120, three 5120x14336 matmuls
    attn = 2 * (5120 * 4096 + 2 * 5120 * 1024 + 4096 * 5120)
    mlp = 2 * 3 * 5120 * 14336
    assert m.token_flops(0) == attn + mlp
    g = model("granite-moe-1b-a400m")
    # router 1024x32, 8 of 32 experts of three 1024x512 matmuls
    assert g.token_flops(0) == (
        2 * (1024 * 1024 + 2 * 1024 * 512 + 1024 * 1024)
        + 2 * 1024 * 32 + 8 * 2 * 3 * 1024 * 512)


def test_step_flops_counts_valid_tokens_context_and_emitting_heads():
    m = model("mistral-nemo-12b-pp4")
    # a decode row at pos 9 (10 keys), a prefill row of 3 at pos 0 (1+2+3
    # keys) that emits
    rows = [(9, 1, True), (0, 3, True)]
    assert counts.visible_pairs(rows) == 10 + 6
    want = 10 * (4 * m.token_flops(0) + 4 * 32 * 128 * 16) \
        + 2 * 2 * 5120 * 131072
    assert counts.step_flops(m, rows) == want
    # a mid-prompt row emits nothing: no head
    assert counts.step_flops(m, [(0, 3, False)]) == \
        10 * (3 * m.token_flops(0) + 4 * 32 * 128 * 6)


def test_paged_attention_cost_by_hand():
    g = model("granite-moe-1b-a400m")
    rows = [(15, 1, True), (32, 64, False)]
    flops, nbytes = counts.paged_attention_cost(g, rows)
    pairs = 16 + (64 * 32 + 64 * 65 // 2)
    assert flops == 24 * 4 * 16 * 64 * pairs
    kv = (16 + 96) * 2 * 8 * 64
    qo = (1 + 64) * 2 * 16 * 64
    assert nbytes == 24 * (kv + qo) * 2
