"""Execution context — the nnabla ``extension context`` adapted to JAX/TPU.

The paper (§2.3, Listing 2) switches backends with a single line::

    nn.set_default_context(get_extension_context('cudnn'))

Here the same one-liner selects the XLA backend, the numeric policy
(paper §3.3 ``type_config``) and — TPU-specific — whether perf-critical ops
lower to Pallas kernels or plain XLA:

    import repro.core as nn
    nn.set_default_context(nn.get_extension_context("tpu", type_config="bf16"))
"""

from __future__ import annotations

import dataclasses
import threading
from typing import Literal

import jax
import jax.numpy as jnp

Backend = Literal["cpu", "tpu", "gpu"]
KernelMode = Literal["xla", "xla_chunked", "pallas", "pallas_interpret"]
# runtime twin of KernelMode for call sites that receive the mode as a
# string (CLI flags, env vars): anything outside this set would silently
# take the compiled-Pallas dispatch branch
KERNEL_MODES: tuple[str, ...] = ("xla", "xla_chunked", "pallas",
                                 "pallas_interpret")


@dataclasses.dataclass(frozen=True)
class Policy:
    """Mixed-precision dtype policy (paper §3.3).

    ``param_dtype``   — storage dtype of trainable parameters.
    ``compute_dtype`` — dtype activations/matmuls run in.
    ``output_dtype``  — dtype losses/logits are produced in (norms, softmax and
    reductions always accumulate in fp32, mirroring the paper's fp32 batch-norm
    inside fp16 networks).
    """

    param_dtype: jnp.dtype = jnp.float32
    compute_dtype: jnp.dtype = jnp.float32
    output_dtype: jnp.dtype = jnp.float32

    @property
    def needs_loss_scaling(self) -> bool:
        # fp16 has a 5-bit exponent -> gradients underflow without scaling.
        # bf16 shares fp32's exponent range -> no scaling required (TPU default).
        return self.compute_dtype == jnp.float16


POLICIES: dict[str, Policy] = {
    "float": Policy(),
    "fp32": Policy(),
    # TPU-native mixed precision: bf16 compute/storage-of-activations,
    # fp32 master params held by the solver.
    "bf16": Policy(jnp.float32, jnp.bfloat16, jnp.bfloat16),
    # Paper-faithful mixed precision (V100 TensorCore style): fp16 storage +
    # compute, fp32 master copy, loss scaling REQUIRED.
    "half": Policy(jnp.float16, jnp.float16, jnp.float16),
    # Fully-cast variant used by some serving configs.
    "pure_bf16": Policy(jnp.bfloat16, jnp.bfloat16, jnp.bfloat16),
}


@dataclasses.dataclass(frozen=True)
class Context:
    backend: Backend = "cpu"
    type_config: str = "float"
    kernels: KernelMode = "xla"
    # device_memory budget used by compile-time checks (bytes; v5e HBM default).
    device_memory: int = 16 * 2**30

    @property
    def policy(self) -> Policy:
        return POLICIES[self.type_config]


class _ContextState(threading.local):
    def __init__(self) -> None:
        self.ctx = Context()
        # auto_forward=True  -> dynamic (define-by-run) graph, paper §2.2 right
        # auto_forward=False -> static (deferred) graph, paper §2.2 left
        self.auto_forward = False


_state = _ContextState()


def get_extension_context(backend: Backend = "cpu", *, type_config: str = "float",
                          kernels: KernelMode = "xla") -> Context:
    if type_config not in POLICIES:
        raise ValueError(
            f"unknown type_config {type_config!r}; one of {sorted(POLICIES)}")
    return Context(backend=backend, type_config=type_config, kernels=kernels)


def resolve_kernels(mode: str | None = None) -> str | None:
    """The kernel mode a serving engine runs, from the platform.

    No mode on a TPU resolves to compiled ``"pallas"``; elsewhere ``None``
    keeps the ambient context (the XLA references by default).
    ``"pallas"`` off a TPU raises here rather than inside Mosaic, which
    lowers for TPUs only (CPU runs take ``"pallas_interpret"``).
    """
    if mode and mode not in KERNEL_MODES:
        raise ValueError(f"unknown kernels mode {mode!r}; "
                         f"one of {KERNEL_MODES}")
    backend = jax.default_backend()
    if not mode:
        return "pallas" if backend == "tpu" else None
    if mode == "pallas" and backend != "tpu":
        raise ValueError(f"kernels='pallas' compiles for a TPU; the "
                         f"{backend!r} backend runs 'pallas_interpret' "
                         f"or the XLA references")
    return mode


def set_default_context(ctx: Context) -> None:
    _state.ctx = ctx


def get_default_context() -> Context:
    return _state.ctx


class context_scope:
    """Temporarily override the default context (used by tests/benchmarks)."""

    def __init__(self, ctx: Context):
        self._ctx = ctx
        self._prev: Context | None = None

    def __enter__(self) -> Context:
        self._prev = _state.ctx
        _state.ctx = self._ctx
        return self._ctx

    def __exit__(self, *exc) -> None:
        assert self._prev is not None
        _state.ctx = self._prev


def set_auto_forward(flag: bool) -> None:
    _state.auto_forward = flag


def get_auto_forward() -> bool:
    return _state.auto_forward


class auto_forward:
    """``with nn.auto_forward():`` — switch to the dynamic graph (paper Fig. 1)."""

    def __init__(self, flag: bool = True):
        self._flag = flag
        self._prev: bool | None = None

    def __enter__(self) -> None:
        self._prev = _state.auto_forward
        _state.auto_forward = self._flag

    def __exit__(self, *exc) -> None:
        assert self._prev is not None
        _state.auto_forward = self._prev
