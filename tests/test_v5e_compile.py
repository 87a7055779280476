"""Compile the main path's kernels and steps for a described TPU v5e.

Nothing runs: each test lowers and compiles for a ``v5e:2x2`` topology that
the installed TPU compiler describes without a chip, so tiling, VMEM and
memory limits that interpret mode cannot see are checked at real widths.
The topology is described inside a fixture, never at import, so every test
worker collects the same tests and only the one given this file loads the
TPU library.
"""

import dataclasses
import math
import os

import jax
import jax.numpy as jnp
import pytest

from repro.configs import RunConfig, get_config
from repro.kernels import ops


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


def _sds(one_chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)


def _kernel_args(name, one_chip):
    """Kernel call and argument shapes at published widths."""
    bf16, f32 = jnp.bfloat16, jnp.float32
    if name == "flash_attention":  # tinyllama: 32 q heads, 4 kv, d_head 64
        return ops.flash_attention, (
            _sds(one_chip, (1, 2048, 32, 64), bf16),
            _sds(one_chip, (1, 2048, 4, 64), bf16),
            _sds(one_chip, (1, 2048, 4, 64), bf16))
    if name == "flash_decode":
        return ops.flash_decode, (
            _sds(one_chip, (4, 1, 32, 64), bf16),
            _sds(one_chip, (4, 2048, 4, 64), bf16),
            _sds(one_chip, (4, 2048, 4, 64), bf16),
            _sds(one_chip, (4,), jnp.int32))
    if name == "fused_rmsnorm":  # tinyllama d_model 2048
        return ops.fused_rmsnorm, (
            _sds(one_chip, (4, 2048, 2048), bf16),
            _sds(one_chip, (2048,), bf16))
    if name == "ssd_chunk_dual":  # mamba2-130m: 24 heads x 64, state 128
        cfg = get_config("mamba2-130m")
        q, n = cfg.ssm_chunk, cfg.ssm_state
        h, p, nc = cfg.ssm_heads, cfg.ssm_head_dim, 2048 // cfg.ssm_chunk
        return ops.ssd_chunk_dual, (
            _sds(one_chip, (1, nc, h, q, p), f32),
            _sds(one_chip, (1, nc, h, q), f32),
            _sds(one_chip, (1, nc, q, n), f32),
            _sds(one_chip, (1, nc, q, n), f32))
    raise KeyError(name)


@pytest.mark.parametrize("name", ["flash_attention", "flash_decode",
                                  "fused_rmsnorm", "ssd_chunk_dual"])
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, args = _kernel_args(name, one_chip)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_decode_step_bracket_on_v5e(one_chip):
    """The analyzer brackets a v5e-compiled tinyllama decode step (2 layers
    at full width): finite, with TP <= CP."""
    from repro.api import analyze
    from repro.models import decode_step, init_cache
    from repro.train.state import abstract_train_state

    cfg = dataclasses.replace(get_config("tinyllama-1.1b"), n_layers=2)
    run = RunConfig(attention_impl="chunked", attention_chunk=64,
                    remat="none", zero=False)

    def on_chip(tree):
        return jax.tree.map(lambda x: _sds(one_chip, x.shape, x.dtype), tree)

    params = on_chip(abstract_train_state(cfg).params)
    cache = on_chip(jax.eval_shape(lambda: init_cache(cfg, 4, 512)))
    tokens = _sds(one_chip, (4, 1), jnp.int32)
    compiled = jax.jit(
        lambda p, c, t: decode_step(p, cfg, run, c, t)
    ).lower(params, cache, tokens).compile()
    report = analyze(compiled, arch="tpu-v5e")
    tp, cp = report.tp_block, report.cp_block
    assert math.isfinite(tp) and math.isfinite(cp)
    assert 0.0 < tp <= cp


HBM = 15.75 * 2**30  # one v5e chip, as its compiler counts it


def _used_bytes(compiled):
    """Device memory one chip needs for the compiled program."""
    ma = compiled.memory_analysis()
    return (ma.argument_size_in_bytes + ma.temp_size_in_bytes
            + ma.output_size_in_bytes - ma.alias_size_in_bytes)


def _compile_train_step(topo, data, model, *, sharded):
    """The train step as train_loop builds it, for tinyllama-1.1b whole and
    the smoke's 2 x 1024 batch, on a (data, model) mesh of described chips;
    ``sharded`` adds FSDP + ZeRO as the smoke's four-chip run does."""
    import numpy as np
    from jax.sharding import AxisType, Mesh

    from repro.configs.base import ShapeConfig
    from repro.distributed import MeshContext, set_mesh_context
    from repro.launch.specs import input_specs
    from repro.launch.train import jit_train_step
    from repro.train.state import abstract_train_state

    cfg = get_config("tinyllama-1.1b")
    run = RunConfig(attention_impl="chunked", attention_chunk=512,
                    remat="full", zero=sharded, fsdp=sharded)
    auto = AxisType.Auto
    devices = np.array(topo.devices[:data * model]).reshape(data, model)
    ctx = MeshContext(Mesh(devices, ("data", "model"), (auto, auto)))
    set_mesh_context(ctx)
    try:
        step, _, _ = jit_train_step(cfg, run, ctx, 2, 1024)
        state = abstract_train_state(cfg)
        compiled = step.lower(
            state, input_specs(cfg, ShapeConfig("t", 1024, 2, "train"))
        ).compile()
    finally:
        set_mesh_context(None)
    state_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(state))
    return compiled, state_bytes


def test_train_step_fits_one_v5e(topo):
    compiled, _ = _compile_train_step(topo, 1, 1, sharded=False)
    assert 0 < _used_bytes(compiled) < HBM


def test_sharded_train_step_fits_2x2_v5e(topo):
    """The four-chip run's step (2x2 data x model, FSDP + ZeRO) fits each
    chip and spreads the train state: each holds under half of it."""
    compiled, state_bytes = _compile_train_step(topo, 2, 2, sharded=True)
    assert 0 < _used_bytes(compiled) < HBM
    held = compiled.memory_analysis().argument_size_in_bytes
    assert held < 0.5 * state_bytes


def test_serve_path_fits_one_v5e(one_chip):
    """The smoke's serve programs on all 22 layers at full width: prefill of
    a 2 x 100 wave, decode with its 108-slot cache, and the 4 x 128 causal
    forward the decode logits are checked against."""
    from repro.models import decode_step, init_cache, prefill
    from repro.models.transformer import forward_hidden, lm_logits
    from repro.train.state import abstract_train_state

    cfg = get_config("tinyllama-1.1b")
    run = RunConfig(attention_impl="chunked", attention_chunk=64,
                    remat="none", zero=False)

    def on_chip(tree):
        return jax.tree.map(lambda x: _sds(one_chip, x.shape, x.dtype), tree)

    params = on_chip(abstract_train_state(cfg).params)
    programs = {
        "prefill": (lambda p, t: prefill(p, cfg, run, t),
                    (params, _sds(one_chip, (2, 100), jnp.int32))),
        "decode": (lambda p, c, t: decode_step(p, cfg, run, c, t),
                   (params, on_chip(jax.eval_shape(
                       lambda: init_cache(cfg, 2, 108))),
                    _sds(one_chip, (2, 1), jnp.int32))),
        "forward": (lambda p, t: lm_logits(
                        p, cfg, forward_hidden(p, cfg, run, t)[0]),
                    (params, _sds(one_chip, (4, 128), jnp.int32))),
    }
    for name, (fn, args) in programs.items():
        compiled = jax.jit(fn).lower(*args).compile()
        assert 0 < _used_bytes(compiled) < HBM, name
