"""The architecture modules (``perfbench/archs/<model_type>.py``): every
configuration has one with every part the harness uses; moving the dense
and mixture-of-experts blocks out of the harness left the weights and the
reference's logits bit for bit as they were; and a new ``model_type`` is
taken by adding files alone."""

import filecmp
import json
import os
import shutil
import subprocess
import sys
import textwrap

import jax
import pytest

from harness import counts, drive, spec
from harness.model import arch, param_count, parse
from harness.smoke import smoke_config

CONFIGS = sorted(p.stem for p in (spec.BENCH / "configs").glob("*.json"))
PARTS = ["from_config", "to_repro", "leaves", "embed", "positions", "layer",
         "head", "token_flops", "attended", "walk_calls", "smoke_cut"]


def run_python(code: str, bench=spec.BENCH, timeout=300) -> dict:
    """Run ``code`` in a fresh process on one CPU core with the benchmark
    at ``bench`` on the path; returns the JSON object it prints last. One
    core, because XLA's CPU matmuls split their work by the cores they
    may use, and so round by them."""
    prelude = textwrap.dedent(f"""
        import os, sys
        os.sched_setaffinity(0, {{min(os.sched_getaffinity(0))}})
        sys.path[:0] = [{str(bench)!r}, {str(spec.REPO / "src")!r}]
    """)
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run([sys.executable, "-c", prelude + textwrap.dedent(code)],
                       env=env, capture_output=True, text=True,
                       timeout=timeout)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


# sha256 (first 16 hex digits) of every leaf of make_params, and of the
# reference's logits in float32 and with the float8 control, at smoke size
# on one CPU core; computed on the commit before the architecture modules
# (a406075), where harness/model.py and harness/reference.py held both
# blocks, with the same code as DIGESTS below
PINNED = {
    "granite-moe-1b-a400m": {"params": "db7cb67ffb9aeb5d",
                             "logits": "859341fb0f84af1b",
                             "control": "b873799a4155ab72"},
    "mistral-nemo-12b-pp4": {"params": "f4b06a694dfc58de",
                             "logits": "d89c7eade405c7da",
                             "control": "d880602e55ad3f4f"},
}

DIGESTS = """
    import hashlib, json
    import numpy as np
    from harness import reference, spec
    from harness.model import make_params, parse
    from harness.smoke import smoke_config

    def digest(arrays):
        h = hashlib.sha256()
        for k in sorted(arrays):
            a = np.asarray(arrays[k])
            h.update(k.encode())
            h.update(str(a.dtype).encode())
            h.update(str(a.shape).encode())
            h.update(a.tobytes())
        return h.hexdigest()[:16]

    SEED = 2**32 + 11
    name = NAME
    m = parse(name, smoke_config(spec.load("configs", name)))
    tokens = np.random.default_rng(7).integers(1, 512, (2, 24)).astype(np.int32)
    want = np.array([[3, 10, 23], [0, 17, 22]], np.int32)
    print(json.dumps({
        "params": digest(make_params(m, SEED)),
        "logits": digest({"l": reference.logits(m, SEED, tokens, want)}),
        "control": digest({"l": reference.logits(m, SEED, tokens, want,
                                                 lowp=True)})}))
"""


@pytest.mark.parametrize("name", sorted(PINNED))
def test_weights_and_reference_are_bit_identical_to_before(name):
    got = run_python(DIGESTS.replace("NAME", repr(name)))
    assert got == PINNED[name]


@pytest.mark.parametrize("name", CONFIGS)
def test_every_configuration_has_an_arch_module(name):
    c = spec.load("configs", name)
    cls = arch(c["model_type"])
    for part in PARTS:
        assert callable(getattr(cls, part)), part
    m = parse(name, c)
    assert (m.name, m.layers) == (name, c["num_hidden_layers"])
    assert (m.vocab, m.d) == (c["vocab_size"], c["hidden_size"])
    assert m.to_repro().n_layers == m.layers
    calls, programs = m.walk_calls()
    assert calls >= 1 and programs >= 1
    # the smoke cut keeps the architecture and its keys
    s = smoke_config(c)
    assert set(s) == set(c) and s["model_type"] == c["model_type"]
    small = parse(name, s)
    assert type(small) is type(m) and small.d < m.d
    # the leaves are the program's parameters, name for name, in the types
    # it serves them in
    drive.setup_context()
    leaves = {n: jax.ShapeDtypeStruct(((depth,) if depth else ()) + shape, dt)
              for n, (depth, shape, dt, _) in small.leaves().items()}
    drive.check_params(small, leaves)
    assert param_count(small) == sum(a.size for a in leaves.values())


def test_unknown_model_type_raises_naming_the_file():
    with pytest.raises(FileNotFoundError, match=r"archs/nosuchmodel\.py"):
        parse("x", {"model_type": "nosuchmodel"})
    with pytest.raises(FileNotFoundError, match="model_type"):
        arch("../harness/model")


def test_windowed_layer_counts_by_hand():
    """A layer that sees the last ``window`` keys: fewer pairs and keys than
    one that sees the whole context, the same where the context is short."""
    rows = [(9, 1, True), (0, 3, True), (30, 4, False)]
    assert counts.visible_pairs(rows) == 10 + 6 + (4 * 30 + 10)
    # window 8: the decode row sees 8 of 10; the short prompt all of 1+2+3;
    # the chunk at 30 sees 8 keys for each of its 4 queries
    assert counts.visible_pairs(rows, window=8) == 8 + 6 + 32
    assert counts.context_keys(rows) == 10 + 3 + 34
    # keys 2..9 for the decode row; 0..2; 23..33 (the first query's 8, then
    # one more for each later query)
    assert counts.context_keys(rows, window=8) == 8 + 3 + 11


TOY = '''
"""A test architecture: the mistral block with a gain on the MLP's input,
and a sliding window of WINDOW keys on every even layer."""

import dataclasses
import functools

import jax
import jax.numpy as jnp

from archs import mistral
from harness import counts
from harness.reference import _rms

WINDOW = 16


@dataclasses.dataclass(frozen=True)
class Model(mistral.Model):
    def leaves(self):
        out = super().leaves()
        out["layers/mlp_in/gamma"] = (self.layers, (self.d,), jnp.float32,
                                      0.0)
        return out

    def window(self, i):
        return WINDOW if i % 2 == 0 else None

    def layer(self, i, x, w, pos, lowp):
        return self._toy_block(x, w, pos, lowp, self.window(i))

    @functools.partial(jax.jit, static_argnums=(0, 4, 5))
    def _toy_block(self, x, w, rope, lowp, window):
        w = {n: a.astype(jnp.float32) for n, a in w.items()}
        x = x + self.attention(x, w, rope, lowp, window=window)
        h = _rms(x, w["layers/ln_mlp/gamma"], self.eps) \\
            * w["layers/mlp_in/gamma"]
        return x + self.ffn_out(h, w, lowp)

    def attended(self, i, rows):
        return (counts.visible_pairs(rows, self.window(i)),
                counts.context_keys(rows, self.window(i)))
'''

TOY_RUN = """
    import json
    import numpy as np
    from harness import counts, reference, spec
    from harness.model import for_cell, make_params, parse
    from harness.smoke import smoke_cell

    SEED = 2**31 + 5
    cell = smoke_cell(spec.load_cell("toy.longdoc"))
    m = for_cell(cell)
    params = make_params(m, SEED)
    tokens = np.random.default_rng(3).integers(1, m.vocab, (2, 40))
    want = np.array([[5, 39], [20, 30]], np.int32)
    toy = np.asarray(reference.logits(m, SEED, tokens, want))
    # the same weights' names and sizes under the mistral block
    plain = parse("plain", dict(cell["model"], model_type="mistral"))
    ref = np.asarray(reference.logits(plain, SEED, tokens, want))
    rows = [(100, 1, True), (0, 8, True)]
    print(json.dumps({
        "arch": type(m).__module__, "layers": m.layers,
        "mlp_in": list(params["layers/mlp_in/gamma"].shape),
        "shape": list(toy.shape), "finite": bool(np.isfinite(toy).all()),
        "differs": float(np.abs(toy - ref).max()),
        "flops": [counts.step_flops(m, rows), counts.step_flops(plain, rows)],
        "walk": list(m.walk_calls())}))
"""


def test_new_model_type_by_adding_files_only(tmp_path):
    """A copy of the benchmark takes a cell of a new architecture from new
    files alone (an arch module, a configuration, a traffic mix and a
    workload): its weights are made and its reference runs at smoke size,
    and no file of the copy was edited."""
    bench = tmp_path / "perfbench"
    shutil.copytree(spec.BENCH, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    (bench / "archs" / "toymistral.py").write_text(TOY)
    c = spec.load("configs", "mistral-nemo-12b-pp4")
    c["model_type"] = "toymistral"
    (bench / "configs" / "toy.json").write_text(json.dumps(c))
    (bench / "traffic" / "toy.json").write_text(
        json.dumps(spec.load("traffic", "longdoc")))
    w = spec.load("workloads", "mistral-nemo.longdoc")
    w.update(config="toy", traffic="toy")
    (bench / "workloads" / "toy.longdoc.json").write_text(json.dumps(w))

    got = run_python(TOY_RUN, bench=bench)
    assert got["arch"] == "archs.toymistral"
    assert got["mlp_in"] == [2, 128] and got["shape"] == [2, 2, 512]
    assert got["finite"] and got["differs"] > 1e-3
    # layer 0 is windowed: the decode row at 100 sees 16 keys, not 101
    toy, plain = got["flops"]
    assert plain - toy == 4 * 4 * 32 * (101 - 16)
    assert got["walk"] == [2, 1]

    added = {"archs/toymistral.py", "configs/toy.json", "traffic/toy.json",
             "workloads/toy.longdoc.json"}
    for p in spec.BENCH.rglob("*"):
        rel = p.relative_to(spec.BENCH)
        if p.is_file() and "__pycache__" not in rel.parts:
            assert filecmp.cmp(p, bench / rel, shallow=False), rel
    copied = {str(p.relative_to(bench)) for p in bench.rglob("*")
              if p.is_file() and "__pycache__" not in p.parts}
    assert copied - added == {
        str(p.relative_to(spec.BENCH)) for p in spec.BENCH.rglob("*")
        if p.is_file() and "__pycache__" not in p.parts}
