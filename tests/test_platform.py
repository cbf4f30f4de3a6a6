"""Platform-chosen kernels, the compile-cache location and chip_smoke.py
on a host without a TPU."""

import dataclasses
import importlib.util
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

import repro.core as nn
from repro.core import context as ctx
from repro.launch import cache
from repro.models.registry import get_model
from repro.serving.engine import ServingEngine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load_chip_smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def granite_smoke():
    from repro.configs import get_arch
    cfg = get_arch("granite-moe-1b-a400m").smoke()
    api = get_model(cfg)
    params = nn.init(lambda t: api.forward(t), jax.random.key(0),
                     jnp.zeros((1, 8), jnp.int32))
    return api, params


def test_resolve_kernels_keeps_the_cpu_default():
    assert jax.default_backend() == "cpu"
    assert ctx.resolve_kernels(None) is None        # ambient context
    for mode in ("xla", "xla_chunked", "pallas_interpret"):
        assert ctx.resolve_kernels(mode) == mode


@pytest.mark.parametrize("mode", ["pallas", "flash"])
def test_resolve_kernels_rejects_pallas_off_tpu_and_typos(mode):
    with pytest.raises(ValueError, match="TPU" if mode == "pallas"
                       else "unknown kernels mode"):
        ctx.resolve_kernels(mode)


def test_engine_kernel_mode_on_cpu(granite_smoke):
    api, params = granite_smoke
    eng = ServingEngine(api, params, max_batch=2, max_seq=32, chunk=8)
    assert eng.kernels is None
    with pytest.raises(ValueError, match="TPU"):
        ServingEngine(api, params, max_batch=2, max_seq=32, chunk=8,
                      kernels="pallas")


def test_compile_cache_location(monkeypatch):
    prev = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        jax.config.update("jax_compilation_cache_dir", None)
        assert cache.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir is None  # JAX's to read
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        got = cache.enable_compile_cache()
        assert got == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_chip_smoke_refuses_cpu(capsys):
    assert load_chip_smoke().main([]) != 0
    out = capsys.readouterr()
    assert '"ok": true' not in out.out
    assert "needs a TPU" in out.err


def test_chip_smoke_alone_fails(tmp_path):
    """A directory holding chip_smoke.py and nothing else of the repo."""
    shutil.copy(os.path.join(REPO, "chip_smoke.py"), tmp_path)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=tmp_path,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout


def test_chip_smoke_one_chip_phase_at_smoke_size(monkeypatch, capsys):
    """The one-chip phase end to end on CPU: the smoke config, tiny
    requests, and the interpret-mode build in place of compiled Pallas."""
    cs = load_chip_smoke()
    real = ctx.resolve_kernels
    monkeypatch.setattr(ctx, "resolve_kernels",
                        lambda mode=None: real(mode) or "pallas_interpret")
    for name, value in dict(KERNELS="pallas_interpret", KERNEL_OP="while",
                            PROMPT_LEN=24, MAX_NEW=4, CHUNK=8,
                            MAX_SEQ=64).items():
        monkeypatch.setattr(cs, name, value)
    from repro.configs import get_arch
    bf16 = dataclasses.replace(ctx.get_default_context(),
                               type_config="pure_bf16")
    with ctx.context_scope(bf16):
        cs.one_chip(get_arch("granite-moe-1b-a400m").smoke(), 0)
    out = capsys.readouterr().out
    assert "served 4/4 requests, 16 tokens" in out
    assert "prefill pallas_interpret vs xla" in out
