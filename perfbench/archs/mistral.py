"""Dense decoder (``model_type`` "mistral"): pre-norm blocks of grouped-query
attention with rotate-half rotary embedding, and a gated SiLU MLP.

An architecture module of the benchmark: ``harness.model`` finds it by the
configuration's ``model_type`` and uses its :class:`Model` for everything
that depends on the architecture:

1. parsing: :meth:`Model.from_config` and :meth:`Model.to_repro`;
2. leaves: :meth:`Model.leaves`, under the program's parameter names;
3. reference math: :meth:`Model.embed`, :meth:`Model.positions`,
   :meth:`Model.layer` and :meth:`Model.head` (float32 at ``HIGHEST``, or
   the float8 control with ``lowp``);
4. counts: :meth:`Model.token_flops`, :meth:`Model.attended` and
   :meth:`Model.walk_calls` (``harness.counts`` also reads ``heads``,
   ``kv_heads`` and ``head_dim`` for the attention's widths);
5. the smoke cut: :meth:`Model.smoke_cut`.

The harness itself reads ``name``, ``layers``, ``vocab`` and ``d``. A new
architecture is a new file beside this one whose ``Model`` has the same
parts; it may subclass this one and replace only what differs, as
:mod:`archs.granitemoe` replaces the MLP.

Per layer: ``x += o(attn(norm(x)))``, then ``x += ffn(norm(x))``; RMSNorm
with the file's ``eps``; attention scaled by ``attention_multiplier``.
Logits are the final norm times the tied embedding or the untied head.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from harness import counts
from harness.model import LOGIT_STD
from harness.reference import _attention, _mm, _rms, _rope, q8


@dataclasses.dataclass(frozen=True)
class Model:
    name: str
    d: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    ffn: int
    vocab: int
    rope_theta: float
    eps: float
    tied: bool
    attn_scale: float

    # -- 1. parsing ------------------------------------------------------ #
    @classmethod
    def from_config(cls, name: str, c: dict) -> "Model":
        return cls(**cls.fields(name, c))

    @classmethod
    def fields(cls, name: str, c: dict) -> dict:
        d, heads = c["hidden_size"], c["num_attention_heads"]
        return dict(
            name=name, d=d, layers=c["num_hidden_layers"], heads=heads,
            kv_heads=c["num_key_value_heads"],
            head_dim=c.get("head_dim") or d // heads,
            ffn=c["intermediate_size"], vocab=c["vocab_size"],
            rope_theta=float(c["rope_theta"]), eps=float(c["rms_norm_eps"]),
            tied=bool(c["tie_word_embeddings"]),
            attn_scale=float(c["attention_multiplier"]))

    def repro_fields(self) -> dict:
        return dict(
            name=self.name, family="dense", n_layers=self.layers,
            d_model=self.d, n_heads=self.heads, n_kv_heads=self.kv_heads,
            d_ff=self.ffn, vocab_size=self.vocab, head_dim=self.head_dim,
            rope_theta=self.rope_theta, tie_embeddings=self.tied,
            norm="rmsnorm", act="silu")

    def to_repro(self):
        """The program's own configuration object for this model."""
        from repro.configs.base import ModelConfig
        return ModelConfig(**self.repro_fields())

    # -- 2. leaves ------------------------------------------------------- #
    def ffn_leaves(self) -> dict:
        d, f = self.d, self.ffn
        return {
            "layers/mlp_gate/kernel": ((d, f), jnp.bfloat16, d ** -0.5),
            "layers/mlp_up/kernel": ((d, f), jnp.bfloat16, d ** -0.5),
            "layers/mlp_down/kernel": ((f, d), jnp.bfloat16, f ** -0.5),
        }

    def leaves(self) -> dict:
        """name -> (depth, shape of one entry, dtype, std): ``depth`` entries
        stacked on axis 0, or 0 for a leaf that is not stacked. A std of 0
        marks a norm gain, drawn around 1."""
        d, hd, L = self.d, self.head_dim, self.layers
        layer = {
            "layers/attn_q/kernel": ((d, self.heads * hd), jnp.bfloat16,
                                     d ** -0.5),
            "layers/attn_k/kernel": ((d, self.kv_heads * hd), jnp.bfloat16,
                                     d ** -0.5),
            "layers/attn_v/kernel": ((d, self.kv_heads * hd), jnp.bfloat16,
                                     d ** -0.5),
            "layers/attn_o/kernel": ((self.heads * hd, d), jnp.bfloat16,
                                     (self.heads * hd) ** -0.5),
            "layers/ln_attn/gamma": ((d,), jnp.float32, 0.0),
            "layers/ln_mlp/gamma": ((d,), jnp.float32, 0.0),
            **self.ffn_leaves(),
        }
        out = {n: (L,) + leaf for n, leaf in layer.items()}
        out["ln_final/gamma"] = (0, (d,), jnp.float32, 0.0)
        if self.tied:
            out["tok_emb/W"] = (0, (self.vocab, d), jnp.bfloat16,
                                LOGIT_STD * d ** -0.5)
        else:
            out["tok_emb/W"] = (0, (self.vocab, d), jnp.bfloat16, 1.0)
            out["lm_head/kernel"] = (0, (d, self.vocab), jnp.bfloat16,
                                     LOGIT_STD * d ** -0.5)
        return out

    # -- 3. reference math ----------------------------------------------- #
    @functools.partial(jax.jit, static_argnums=(0, 3))
    def embed(self, tokens, g, lowp):
        x = jnp.take(g["tok_emb/W"].astype(jnp.float32), tokens, axis=0)
        return q8(x, -1) if lowp else x

    def positions(self, S: int):
        """Rotary tables (cos, sin) of positions ``0 .. S - 1``, which every
        layer shares."""
        hd = self.head_dim
        inv = 1.0 / (self.rope_theta ** (jnp.arange(0, hd, 2,
                                                   dtype=jnp.float32) / hd))
        f = jnp.arange(S, dtype=jnp.float32)[:, None] * inv[None, :]
        return jnp.cos(f)[None, :, None, :], jnp.sin(f)[None, :, None, :]

    def layer(self, i: int, x, w, pos, lowp: bool):
        """Layer ``i`` on the residual stream ``x`` (N, S, d), with that
        layer's leaves ``w``; every layer of this architecture is alike."""
        return self._block(x, w, pos, lowp)

    @functools.partial(jax.jit, static_argnums=(0, 4))
    def _block(self, x, w, rope, lowp):
        w = {n: a.astype(jnp.float32) for n, a in w.items()}
        x = x + self.attention(x, w, rope, lowp)
        h = _rms(x, w["layers/ln_mlp/gamma"], self.eps)
        return x + self.ffn_out(h, w, lowp)

    def attention(self, x, w, rope, lowp, window=None):
        cos, sin = rope
        N, S, d = x.shape
        hd = self.head_dim
        h = _rms(x, w["layers/ln_attn/gamma"], self.eps)
        q = _mm("nsd,de->nse", h, w["layers/attn_q/kernel"], lowp)
        k = _mm("nsd,de->nse", h, w["layers/attn_k/kernel"], lowp)
        v = _mm("nsd,de->nse", h, w["layers/attn_v/kernel"], lowp)
        q = _rope(q.reshape(N, S, self.heads, hd), cos, sin)
        k = _rope(k.reshape(N, S, self.kv_heads, hd), cos, sin)
        v = v.reshape(N, S, self.kv_heads, hd)
        a = _attention(q, k, v, self.attn_scale, lowp, window=window)
        return _mm("nse,ed->nsd", a.reshape(N, S, self.heads * hd),
                   w["layers/attn_o/kernel"], lowp)

    def ffn_out(self, h, w, lowp):
        """The gated SiLU MLP."""
        g = _mm("nsd,df->nsf", h, w["layers/mlp_gate/kernel"], lowp)
        u = _mm("nsd,df->nsf", h, w["layers/mlp_up/kernel"], lowp)
        return _mm("nsf,fd->nsd", jax.nn.silu(g) * u,
                   w["layers/mlp_down/kernel"], lowp)

    @functools.partial(jax.jit, static_argnums=(0, 4))
    def head(self, x, want, g, lowp):
        """Logits (N, P, V) at positions ``want`` (N, P)."""
        x = jnp.take_along_axis(x, want[..., None], axis=1)
        x = _rms(x, g["ln_final/gamma"].astype(jnp.float32), self.eps)
        if self.tied:
            return _mm("npd,vd->npv", x, g["tok_emb/W"].astype(jnp.float32),
                       lowp, -1, -1)
        return _mm("npd,dv->npv", x, g["lm_head/kernel"].astype(jnp.float32),
                   lowp)

    # -- 4. counts ------------------------------------------------------- #
    def ffn_flops(self) -> int:
        return 3 * 2 * self.d * self.ffn

    def token_flops(self, i: int) -> int:
        """Matmul FLOPs of one token through layer ``i`` (weights only)."""
        d, hd = self.d, self.head_dim
        attn = 2 * d * (self.heads + 2 * self.kv_heads) * hd \
            + 2 * self.heads * hd * d
        return attn + self.ffn_flops()

    def attended(self, i: int, rows) -> tuple[int, int]:
        """(query, key) pairs layer ``i`` attends over for a step's rows, and
        the keys it reads: every layer sees each row's whole context."""
        return counts.visible_pairs(rows), counts.context_keys(rows)

    def walk_calls(self) -> tuple[int, int]:
        """The paged walk's calls in one step, and its distinct programs:
        one call per layer, all of one program."""
        return self.layers, 1

    # -- 5. smoke cut ---------------------------------------------------- #
    @classmethod
    def smoke_cut(cls, c: dict) -> None:
        """Cut the configuration ``c`` in place to a size the CPU runs in
        seconds."""
        c.update(hidden_size=128, intermediate_size=128, num_hidden_layers=2,
                 num_attention_heads=4, num_key_value_heads=2, head_dim=32,
                 vocab_size=512, attention_multiplier=32 ** -0.5)
