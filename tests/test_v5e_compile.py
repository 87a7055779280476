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
import re

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


@pytest.mark.parametrize("name,n_layers,cache_len", [
    ("starcoder2-15b", 2, 1027),  # dense, G = 12, a cache no block divides
    ("qwen3-8b", 2, 1280),  # dense, 8 KV heads, qk-norm
    ("phi-3-vision-4.2b", 2, 1280),  # vlm, 32 KV heads of 96
    ("deepseek-moe-16b", 2, 1280),  # moe, a dense layer first, 16 KV heads
    ("phi3.5-moe-42b-a6.6b", 2, 1280),  # moe, 8 KV heads
    ("whisper-base", 2, 448),  # audio self-attention, 8 KV heads of 64
    ("zamba2-2.7b", 13, 4100),  # hybrid: 2 calls, 32 KV heads of 160
])
def test_decode_step_kernel_per_family_on_v5e(one_chip, name, n_layers,
                                              cache_len):
    """Each attention family's decode, at its published widths, compiles
    for a v5e with the flash-decode kernel reading its cache: the kernel's
    blocks fit VMEM at every KV-head count and head width."""
    from repro.kernels.decode_attention import NAME
    from repro.models import decode_step, init_cache
    from repro.train.state import abstract_train_state

    cfg = dataclasses.replace(get_config(name), n_layers=n_layers)
    run = RunConfig(attention_impl="chunked", attention_chunk=512,
                    remat="none", zero=False)
    b = 8

    def on_chip(tree):
        return jax.tree.map(lambda x: _sds(one_chip, x.shape, x.dtype), tree)

    params = on_chip(abstract_train_state(cfg).params)
    cache = on_chip(jax.eval_shape(lambda: init_cache(cfg, b, cache_len)))
    text = jax.jit(lambda p, c, tok: decode_step(p, cfg, run, c, tok)).lower(
        params, cache, _sds(one_chip, (b, 1), jnp.int32)).compile().as_text()
    assert any(NAME in line and 'custom_call_target="tpu_custom_call"' in line
               for line in text.splitlines())


def _computations(text):
    """{computation name: its instruction lines} of a compiled module."""
    comps, cur = {}, None
    for line in text.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head:
            cur = comps.setdefault(head.group(1), [])
        elif cur is not None and line.strip() not in ("", "}"):
            cur.append(line.strip())
    return comps


def _instr(line):
    """(name, opcode, [array dims of the result]) of one instruction."""
    m = re.match(r"^(?:ROOT )?%(\S+) = (.*?) ([\w-]+)\(", line)
    if not m:
        return None
    dims = [tuple(int(d) for d in ds.split(",") if d)
            for ds in re.findall(r"\w+\[([\d,]*)\]", m.group(2))]
    return m.group(1), m.group(3), dims


def test_stacked_decode_in_place_on_v5e(one_chip):
    """Decode at yi-9b widths (2 layers, the serve cell's batch 64 and cache
    1280) reads and writes its stacked KV cache in place: the flash-decode
    kernel runs by name inside the layer loop, nothing in the loop makes a
    tensor of a layer's cache or of the stack besides the in-place
    one-row updates, and the only whole-cache copies are K and V on entry
    (the jit is not donated its cache).  At a stack of a few tens of MB the
    compiler may stage it in VMEM instead, so the cell's batch is kept."""
    from repro.kernels.decode_attention import NAME
    from repro.models import decode_step, init_cache
    from repro.train.state import abstract_train_state

    cfg = dataclasses.replace(get_config("yi-9b"), n_layers=2)
    run = RunConfig(attention_impl="chunked", attention_chunk=512,
                    remat="none", zero=False)
    b, t = 64, 1280

    def on_chip(tree):
        return jax.tree.map(lambda x: _sds(one_chip, x.shape, x.dtype), tree)

    params = on_chip(abstract_train_state(cfg).params)
    cache = on_chip(jax.eval_shape(lambda: init_cache(cfg, b, t)))
    text = jax.jit(lambda p, c, tok: decode_step(p, cfg, run, c, tok)).lower(
        params, cache, _sds(one_chip, (b, 1), jnp.int32)).compile().as_text()
    comps = _computations(text)
    stack = cache["k"].shape

    def squeezed(dims):
        return tuple(sorted(d for d in dims if d != 1))

    cache_sized = {squeezed(stack), squeezed(stack[1:])}  # stack, a layer

    def in_place_update(op, line):
        if op == "dynamic-update-slice":
            return True
        called = re.search(r"calls=%?([\w.\-]+)", line)
        return op == "fusion" and called is not None and any(
            l.startswith("ROOT") and " dynamic-update-slice(" in l
            for l in comps[called.group(1)])

    loops = [re.search(r"body=%?([\w.\-]+)", l).group(1)
             for lines in comps.values() for l in lines if " while(" in l]
    body = [i + (line,) for loop in loops for line in comps[loop]
            if (i := _instr(line))]
    assert any(NAME in name and op == "custom-call"
               for name, op, _, _ in body)
    made = [name for name, op, dims, line in body
            if op not in ("parameter", "get-tuple-element", "tuple", "bitcast")
            and not in_place_update(op, line)
            and any(squeezed(d) in cache_sized for d in dims)]
    assert made == [], made
    instrs = [i for lines in comps.values() for i in map(_instr, lines) if i]
    whole = [name for name, op, dims in instrs
             if op in ("copy", "copy-start")
             and any(squeezed(d) == squeezed(stack) for d in dims)]
    assert len(whole) <= 2, whole


def test_hybrid_decode_in_place_on_v5e(one_chip):
    """Zamba2-7B's decode at the serve cell's widths (12 layers: two
    invocations, blocks 0 and 1; batch 8, cache 1280) compiles with the
    flash-decode kernel reading the stacked KV cache once per invocation
    (32 KV heads of 224 lanes), and each run of Mamba-2 layers a loop that
    updates the stacked SSM and convolution states in place: nothing in a
    loop makes a tensor of a whole stack besides those one-layer updates.
    The engine's decode is donated its cache, and the stack keeps its heads
    in 256 lanes (``kv_head_align``), which the runtime lays out as the
    kernel reads them (224-lane heads it lays out positions-minor): no
    whole copy of K or V."""
    from repro.kernels.decode_attention import NAME
    from repro.models import init_cache
    from repro.models.transformer import hybrid_runs
    from repro.serving import ServeEngine
    from repro.train.state import abstract_train_state

    cfg = dataclasses.replace(get_config("zamba2-7b"), n_layers=12)
    assert len(cfg.hybrid_ids) == 2
    run = RunConfig(attention_impl="chunked", attention_chunk=512,
                    remat="none", zero=False)
    b, t = 8, 1280

    def on_chip(tree):
        return jax.tree.map(lambda x: _sds(one_chip, x.shape, x.dtype), tree)

    params = on_chip(abstract_train_state(cfg).params)
    cache = on_chip(jax.eval_shape(lambda: init_cache(cfg, b, t)))
    assert cache["k"].shape == (2, b, t, 32, 256)
    decode = ServeEngine(cfg, params, run=run, batch_size=b).decode
    text = decode.lower(params, cache, _sds(one_chip, (b, 1), jnp.int32)
                        ).compile().as_text()
    comps = _computations(text)
    instrs = [i + (line,) for lines in comps.values() for line in lines
              if (i := _instr(line))]
    assert sum(NAME in name and op == "custom-call"
               for name, op, _, _ in instrs) == len(cfg.hybrid_ids)

    def squeezed(dims):
        return tuple(sorted(d for d in dims if d != 1))

    stacks = {squeezed(cache[k].shape) for k in ("k", "ssm", "conv")}

    def in_place_update(op, line):
        if op == "dynamic-update-slice":
            return True
        called = re.search(r"calls=%?([\w.\-]+)", line)
        return op == "fusion" and called is not None and any(
            l.startswith("ROOT") and " dynamic-update-slice(" in l
            for l in comps[called.group(1)])

    loops = [re.search(r"body=%?([\w.\-]+)", l).group(1)
             for lines in comps.values() for l in lines if " while(" in l]
    runs = hybrid_runs(cfg)  # a loop a run of more than one layer
    assert len(loops) == sum(end - first > 1 for _, first, end in runs)
    made = [name for loop in loops for line in comps[loop]
            if (i := _instr(line))
            for name, op, dims in [i]
            if op not in ("parameter", "get-tuple-element", "tuple", "bitcast")
            and not in_place_update(op, line)
            and any(squeezed(d) in stacks for d in dims)]
    assert made == [], made
    whole = [name for name, op, dims, _ in instrs
             if op in ("copy", "copy-start")
             and any(squeezed(d) == squeezed(cache["k"].shape) for d in dims)]
    assert whole == [], whole


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
