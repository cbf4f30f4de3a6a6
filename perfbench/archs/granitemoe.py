"""Mixture-of-experts decoder (``model_type`` "granitemoe"): the blocks of
:mod:`archs.mistral` with the MLP replaced by experts.

The softmax router picks each token's top-k experts and renormalizes their
gates to sum to 1; every chosen expert is applied to every token (no
capacity, nothing dropped) and the outputs are summed by gate.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp

from archs import mistral
from harness.reference import HI, _mm


@dataclasses.dataclass(frozen=True)
class Model(mistral.Model):
    experts: int
    top_k: int
    capacity_factor: float
    moe_group_size: int

    @classmethod
    def fields(cls, name: str, c: dict) -> dict:
        return dict(
            super().fields(name, c), experts=c["num_local_experts"],
            top_k=c["num_experts_per_tok"],
            capacity_factor=float(c.get("capacity_factor", 1.25)),
            moe_group_size=int(c.get("moe_group_size", 2048)))

    def repro_fields(self) -> dict:
        return dict(
            super().repro_fields(), family="moe", n_experts=self.experts,
            top_k=self.top_k, capacity_factor=self.capacity_factor,
            moe_group_size=self.moe_group_size)

    def ffn_leaves(self) -> dict:
        d, E, f = self.d, self.experts, self.ffn
        return {
            # a decisive router, as trained ones are: top-k choices that do
            # not flip on rounding
            "layers/moe_router/kernel": ((d, E), jnp.float32, d ** -0.5),
            "layers/moe_wi_gate": ((E, d, f), jnp.bfloat16, d ** -0.5),
            "layers/moe_wi_up": ((E, d, f), jnp.bfloat16, d ** -0.5),
            "layers/moe_wo": ((E, f, d), jnp.bfloat16, f ** -0.5),
        }

    def ffn_out(self, h, w, lowp):
        probs = jax.nn.softmax(
            jnp.einsum("nsd,de->nse", h, w["layers/moe_router/kernel"],
                       precision=HI), axis=-1)
        top, idx = jax.lax.top_k(probs, self.top_k)
        top = top / jnp.sum(top, -1, keepdims=True)
        gates = jnp.sum(jax.nn.one_hot(idx, self.experts) * top[..., None], -2)
        g = _mm("nsd,edf->nsef", h, w["layers/moe_wi_gate"], lowp, -1, 1)
        u = _mm("nsd,edf->nsef", h, w["layers/moe_wi_up"], lowp, -1, 1)
        act = jax.nn.silu(g) * u * gates[..., None]
        return _mm("nsef,efd->nsd", act, w["layers/moe_wo"], lowp, -1, 1)

    def ffn_flops(self) -> int:
        """The router, and the top-k experts' three matmuls."""
        return 2 * self.d * self.experts + self.top_k * 3 * 2 * self.d \
            * self.ffn

    @classmethod
    def smoke_cut(cls, c: dict) -> None:
        super().smoke_cut(c)
        c.update(num_local_experts=4, num_experts_per_tok=2,
                 moe_group_size=64)
