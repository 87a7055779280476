"""Launcher tests (CPU): the training loop on the elastic mesh, and where
the persistent compilation cache lives."""

import math

import jax
import pytest

from repro.configs import RunConfig, get_config, tiny_variant
from repro.launch import compile_cache
from repro.launch.mesh import make_elastic_mesh_context
from repro.launch.train import train_loop


def test_train_loop_two_tiny_steps_on_1x1_mesh():
    cfg = tiny_variant(get_config("tinyllama-1.1b"))
    run = RunConfig(attention_impl="chunked", attention_chunk=16,
                    remat="full", zero=False, warmup_steps=2, total_steps=4)
    ctx = make_elastic_mesh_context(1)
    assert dict(ctx.mesh.shape) == {"data": 1, "model": 1}
    out = train_loop(cfg, run, steps=2, global_batch=2, seq_len=32,
                     mesh_ctx=ctx, log_every=1)
    assert [m["step"] for m in out.metrics] == [1, 2]
    for m in out.metrics:
        assert math.isfinite(m["loss"]) and math.isfinite(m["grad_norm"])
    assert abs(out.metrics[0]["loss"] - math.log(cfg.vocab)) < 0.1 * math.log(
        cfg.vocab)
    assert int(out.state.step) == 2
    # The state comes back placed by the mesh's sharding rules.
    embed = out.state.params["embed"]
    assert embed.sharding.mesh.shape == ctx.mesh.shape


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_env_wins(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before  # left to JAX


def test_compile_cache_falls_back_to_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(compile_cache.CHECKOUT / ".jax_cache")
    assert (compile_cache.CHECKOUT / "chip_smoke.py").is_file()
    assert jax.config.jax_compilation_cache_dir == path
