"""Sharding-rule unit tests + a real multi-device dry-run smoke test.

The smoke test runs ``repro.launch.dryrun`` machinery in a subprocess with 16
forced host devices and a scaled-down mesh — proving lower+compile+roofline
works end-to-end with SPMD partitioning without the 512-device cost."""

import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import RunConfig, get_config, tiny_variant
from repro.distributed import MeshContext
from repro.distributed.sharding import _sanitize, spec_for_path


class FakeMesh:
    def __init__(self, shape):
        self.shape = shape
        self.axis_names = tuple(shape)


def ctx(shape):
    return MeshContext.__new__(MeshContext), shape  # not used directly


def make_ctx(shape):
    c = MeshContext.__new__(MeshContext)
    c.mesh = FakeMesh(shape)
    c.data_axes = tuple(a for a in ("pod", "data") if a in shape)
    c.model_axis = "model"
    return c


def test_sanitize_drops_nondivisible():
    c = make_ctx({"data": 4, "model": 8})
    spec = _sanitize(c, (16, 10), P("data", "model"))
    assert spec == P("data")  # 10 % 8 != 0 -> replicated


def test_sanitize_drops_missing_axis():
    c = make_ctx({"data": 4, "model": 4})
    spec = _sanitize(c, (16, 16), P(("pod", "data"), "model"))
    assert spec == P("data", "model")


def test_param_rules():
    assert spec_for_path(("embed",), (1000, 64)) == P("model", None)
    assert spec_for_path(("layers", "attn", "wq"), (4, 64, 128)) == \
        P(None, None, "model")
    assert spec_for_path(("layers", "attn", "wo"), (4, 128, 64)) == \
        P(None, "model", None)
    assert spec_for_path(("layers", "moe", "moe_wi"), (4, 8, 64, 128)) == \
        P(None, "model", None, None)
    assert spec_for_path(("final_norm",), (64,)) == P()


DRYRUN_SMOKE = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=16"
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs import RunConfig, SHAPES, get_config, tiny_variant
    from repro.configs.base import ShapeConfig
    from repro.core.hlo import roofline_from_compiled, hlo_loop_carried
    from repro.distributed import MeshContext, set_mesh_context
    from repro.launch.specs import batch_shardings, cache_shardings, input_specs
    from repro.train import make_train_step
    from repro.train.state import abstract_train_state, state_shardings

    auto = jax.sharding.AxisType.Auto
    mesh = jax.make_mesh((4, 4), ("data", "model"), (auto, auto))
    ctx = MeshContext(mesh=mesh, data_axes=("data",), model_axis="model")
    set_mesh_context(ctx)

    cfg = tiny_variant(get_config("{arch}"))
    shape = ShapeConfig("smoke", seq_len=128, global_batch=8, kind="train")
    run = RunConfig(attention_impl="chunked", attention_chunk=64,
                    remat="full", zero=True, fsdp=True, seq_shard=True)
    specs = input_specs(cfg, shape)
    state = abstract_train_state(cfg)
    st_shard = state_shardings(state, ctx, run)
    bshard = batch_shardings(specs, ctx)
    step = make_train_step(cfg, run)
    lowered = jax.jit(step, in_shardings=(st_shard, bshard),
                      out_shardings=(st_shard, None),
                      donate_argnums=(0,)).lower(state, specs)
    compiled = lowered.compile()
    ma = compiled.memory_analysis()
    assert ma.temp_size_in_bytes > 0
    rep = roofline_from_compiled(compiled, name="smoke")
    assert rep.num_partitions == 16
    assert rep.terms["MXU"] > 0 and rep.terms["HBM"] > 0
    lcd = hlo_loop_carried(compiled)
    print("SMOKE_OK", rep.dominant, len(lcd.chains))
""")


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "deepseek-moe-16b",
                                  "mamba2-130m"])
def test_dryrun_smoke_16dev(arch):
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env["JAX_PLATFORMS"] = "cpu"  # never reach for an accelerator
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, "-c", DRYRUN_SMOKE.format(arch=arch)],
        capture_output=True, text=True, timeout=540, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "SMOKE_OK" in proc.stdout


def test_serve_engine_roundtrip():
    from repro.models import init_params
    from repro.serving import ServeEngine

    cfg = tiny_variant(get_config("tinyllama-1.1b"))
    params = init_params(cfg, jax.random.PRNGKey(0))
    engine = ServeEngine(cfg, params, batch_size=2)
    prompts = [[1, 2, 3, 4], [5, 6, 7, 8, 9], [10, 11]]
    results = engine.generate(prompts, max_new_tokens=4)
    assert len(results) == 3
    assert all(len(r.tokens) == 4 for r in results)
    assert [r.request_id for r in results] == [0, 1, 2]
