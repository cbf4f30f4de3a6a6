"""A configuration file as the benchmark reads it, and its weights.

The weights are the benchmark's, not the program's: one function of
``--seed`` per leaf, under the program's parameter names. The program gets
them all at once from one jitted call (:func:`make_params`); the plain
reference makes the same leaves again, one layer at a time
(:func:`layer_params`), so that it shares nothing with the program.
"""

from __future__ import annotations

import dataclasses
import functools
import math
import zlib

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Model:
    name: str
    family: str                 # "dense" | "moe"
    d: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int
    experts: int
    top_k: int
    rope_theta: float
    eps: float
    tied: bool
    attn_scale: float
    capacity_factor: float
    moe_group_size: int

    @classmethod
    def from_config(cls, name: str, c: dict) -> "Model":
        d, heads = c["hidden_size"], c["num_attention_heads"]
        head_dim = c.get("head_dim") or d // heads
        return cls(
            name=name, family="moe" if c.get("num_local_experts") else "dense",
            d=d, layers=c["num_hidden_layers"], heads=heads,
            kv_heads=c["num_key_value_heads"], head_dim=head_dim,
            ffn=c["intermediate_size"], vocab=c["vocab_size"],
            experts=c.get("num_local_experts", 0),
            top_k=c.get("num_experts_per_tok", 0),
            rope_theta=float(c["rope_theta"]), eps=float(c["rms_norm_eps"]),
            tied=bool(c["tie_word_embeddings"]),
            attn_scale=float(c["attention_multiplier"]),
            capacity_factor=float(c.get("capacity_factor", 1.25)),
            moe_group_size=int(c.get("moe_group_size", 2048)))

    def kv_bytes_per_token(self, itemsize: int = 2) -> int:
        return 2 * self.layers * self.kv_heads * self.head_dim * itemsize

    def to_repro(self):
        """The program's own configuration object for this model."""
        from repro.configs.base import ModelConfig
        return ModelConfig(
            name=self.name, family=self.family, n_layers=self.layers,
            d_model=self.d, n_heads=self.heads, n_kv_heads=self.kv_heads,
            d_ff=self.ffn, vocab_size=self.vocab, head_dim=self.head_dim,
            n_experts=self.experts, top_k=self.top_k,
            capacity_factor=self.capacity_factor,
            moe_group_size=self.moe_group_size, rope_theta=self.rope_theta,
            tie_embeddings=self.tied, norm="rmsnorm", act="silu")


# logits of a random model have this standard deviation: large enough that
# the best token stands apart from most of the vocabulary, as in a trained
# model, so a wrong token shows as a wide gap
LOGIT_STD = 4.0


def layer_leaves(m: Model) -> dict[str, tuple[tuple[int, ...], object, float]]:
    """Per-layer leaves: name -> (shape of one layer, dtype, std). A std
    of 0 marks a norm gain, drawn around 1."""
    d, hd = m.d, m.head_dim
    out = {
        "layers/attn_q/kernel": ((d, m.heads * hd), jnp.bfloat16, d ** -0.5),
        "layers/attn_k/kernel": ((d, m.kv_heads * hd), jnp.bfloat16, d ** -0.5),
        "layers/attn_v/kernel": ((d, m.kv_heads * hd), jnp.bfloat16, d ** -0.5),
        "layers/attn_o/kernel": ((m.heads * hd, d), jnp.bfloat16,
                                 (m.heads * hd) ** -0.5),
        "layers/ln_attn/gamma": ((d,), jnp.float32, 0.0),
        "layers/ln_mlp/gamma": ((d,), jnp.float32, 0.0),
    }
    if m.family == "moe":
        E, f = m.experts, m.ffn
        out.update({
            # a decisive router, as trained ones are: top-k choices that do
            # not flip on rounding
            "layers/moe_router/kernel": ((d, E), jnp.float32, d ** -0.5),
            "layers/moe_wi_gate": ((E, d, f), jnp.bfloat16, d ** -0.5),
            "layers/moe_wi_up": ((E, d, f), jnp.bfloat16, d ** -0.5),
            "layers/moe_wo": ((E, f, d), jnp.bfloat16, f ** -0.5),
        })
    else:
        f = m.ffn
        out.update({
            "layers/mlp_gate/kernel": ((d, f), jnp.bfloat16, d ** -0.5),
            "layers/mlp_up/kernel": ((d, f), jnp.bfloat16, d ** -0.5),
            "layers/mlp_down/kernel": ((f, d), jnp.bfloat16, f ** -0.5),
        })
    return out


def global_leaves(m: Model) -> dict[str, tuple[tuple[int, ...], object, float]]:
    d = m.d
    out = {"ln_final/gamma": ((d,), jnp.float32, 0.0)}
    if m.tied:
        out["tok_emb/W"] = ((m.vocab, d), jnp.bfloat16, LOGIT_STD * d ** -0.5)
    else:
        out["tok_emb/W"] = ((m.vocab, d), jnp.bfloat16, 1.0)
        out["lm_head/kernel"] = ((d, m.vocab), jnp.bfloat16,
                                 LOGIT_STD * d ** -0.5)
    return out


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


def _layer(m: Model, key, layer):
    key = jax.random.fold_in(key, layer)
    return {n: _leaf(key, n, s, dt, std)
            for n, (s, dt, std) in layer_leaves(m).items()}


def _globals(m: Model, key):
    return {n: _leaf(key, n, s, dt, std)
            for n, (s, dt, std) in global_leaves(m).items()}


def make_params(m: Model, seed: int) -> dict[str, jax.Array]:
    """Every leaf, layers stacked on axis 0, made on the device in one
    jitted call."""
    def build(key):
        stacked = jax.vmap(lambda l: _layer(m, key, l))(
            jnp.arange(m.layers, dtype=jnp.int32))
        return {**stacked, **_globals(m, key)}
    return jax.jit(build)(seed_key(seed))


def layer_params(m: Model, seed: int, layer: int) -> dict[str, jax.Array]:
    """One layer's leaves, as :func:`make_params` stacks them."""
    fn = _jitted_layer(m)
    return fn(seed_key(seed), jnp.int32(layer))


def global_params(m: Model, seed: int) -> dict[str, jax.Array]:
    return jax.jit(lambda k: _globals(m, k))(seed_key(seed))


@functools.cache
def _jitted_layer(m: Model):
    return jax.jit(lambda k, l: _layer(m, k, l))


def param_count(m: Model) -> int:
    n = sum(math.prod(s) for s, _, _ in layer_leaves(m).values()) * m.layers
    return n + sum(math.prod(s) for s, _, _ in global_leaves(m).values())
