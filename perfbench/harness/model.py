"""A configuration file as the benchmark reads it, and its weights.

What depends on the architecture lives in ``archs/<model_type>.py``, found
by the configuration's ``model_type`` as metric readers are found by name:
its ``Model`` parses the file, lists the leaves, and holds the reference's
layer equations and the step's counts. A new architecture is a new file
there and nothing else.

The weights are the benchmark's, not the program's: one function of
``--seed`` per leaf, under the program's parameter names. The program gets
them all at once from one jitted call (:func:`make_params`); the plain
reference makes the same leaves again, one layer at a time
(:func:`layer_params`), so that it shares nothing with the program.
"""

from __future__ import annotations

import functools
import importlib
import math
import re
import zlib

import jax
import jax.numpy as jnp

from harness import spec

ARCHS = spec.BENCH / "archs"

# logits of a random model have this standard deviation: large enough that
# the best token stands apart from most of the vocabulary, as in a trained
# model, so a wrong token shows as a wide gap
LOGIT_STD = 4.0


def arch(model_type: str) -> type:
    """The ``Model`` class of ``archs/<model_type>.py``."""
    path = ARCHS / f"{model_type}.py"
    if not re.fullmatch(r"[A-Za-z0-9_][A-Za-z0-9_-]*", model_type) \
            or not path.is_file():
        raise FileNotFoundError(
            f"no architecture for model_type {model_type!r} ({path})")
    return importlib.import_module(f"archs.{model_type}").Model


def parse(name: str, config: dict):
    """The model object of the configuration ``config``, named ``name``."""
    return arch(config["model_type"]).from_config(name, config)


def for_cell(cell: dict):
    """The model object of a cell loaded by :func:`spec.load_cell`."""
    return parse(cell["config"], cell["model"])


def seed_key(seed: int) -> jax.Array:
    """A PRNG key from a seed of any size, also one of more than 32 bits."""
    seed = int(seed)
    key = jax.random.key(seed % (1 << 31))
    return jax.random.fold_in(key, (seed >> 31) % (1 << 31))


def _leaf(key, name: str, shape, dtype, std):
    key = jax.random.fold_in(key, zlib.crc32(name.encode()) & 0x7FFFFFFF)
    z = jax.random.normal(key, shape, jnp.float32)
    x = 1.0 + 0.1 * z if std == 0.0 else std * z
    return x.astype(dtype)


def _entry(key, name, index, shape, dtype, std):
    """Entry ``index`` of a stacked leaf."""
    return _leaf(jax.random.fold_in(key, index), name, shape, dtype, std)


def _globals(m, key):
    return {n: _leaf(key, n, s, dt, std)
            for n, (depth, s, dt, std) in m.leaves().items() if not depth}


def make_params(m, seed: int) -> dict[str, jax.Array]:
    """Every leaf, stacked leaves on axis 0, made on the device in one
    jitted call."""
    def build(key):
        out = {}
        for n, (depth, s, dt, std) in m.leaves().items():
            if depth:
                out[n] = jax.vmap(
                    lambda i, n=n, s=s, dt=dt, std=std:
                    _entry(key, n, i, s, dt, std))(
                        jnp.arange(depth, dtype=jnp.int32))
        return {**out, **_globals(m, key)}
    return jax.jit(build)(seed_key(seed))


def layer_params(m, seed: int, layer: int) -> dict[str, jax.Array]:
    """Entry ``layer`` of every stacked leaf that deep, as
    :func:`make_params` stacks them."""
    names = tuple(n for n, (depth, *_) in m.leaves().items() if depth > layer)
    return _jitted_layer(m, names)(seed_key(seed), jnp.int32(layer))


def global_params(m, seed: int) -> dict[str, jax.Array]:
    return jax.jit(lambda k: _globals(m, k))(seed_key(seed))


@functools.cache
def _jitted_layer(m, names: tuple[str, ...]):
    leaves = m.leaves()
    return jax.jit(lambda k, i: {n: _entry(k, n, i, *leaves[n][1:])
                                 for n in names})


def param_count(m) -> int:
    return sum(max(depth, 1) * math.prod(s)
               for depth, s, _, _ in m.leaves().values())
