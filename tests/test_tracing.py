"""What the program gives a profiler trace to read (CPU): the module names of
the jitted prefill, decode and train step, the named scopes in the train
step's compiled metadata, the compile counter, and that the jitted prefill
serves the tokens the eager one did."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import RunConfig, get_config, tiny_variant
from repro.distributed import set_mesh_context
from repro.launch import compile_cache
from repro.launch.mesh import make_elastic_mesh_context
from repro.launch.train import jit_train_step
from repro.models import init_params, prefill
from repro.serving import ServeEngine
from repro.train import init_train_state

RUN = RunConfig(attention_impl="chunked", attention_chunk=16, remat="none",
                zero=False)


def module_name(lowered) -> str:
    return re.match(r"module @(\S+)", lowered.as_text()).group(1)


@pytest.fixture(scope="module")
def engine():
    cfg = tiny_variant(get_config("tinyllama-1.1b"))
    return ServeEngine(cfg, init_params(cfg, jax.random.PRNGKey(0)), run=RUN,
                       batch_size=2)


def test_serve_programs_have_stable_names(engine):
    tokens = jnp.zeros((2, 8), jnp.int32)
    assert module_name(engine.prefill.lower(
        engine.params, engine.cfg, engine.run, tokens)) == "jit_prefill"
    _, cache = engine.prefill_wave([[1] * 8, [2] * 8], max_new_tokens=4)
    # The decode program's name is what the chip benchmark finds it by.
    assert module_name(engine.decode.lower(
        engine.params, cache, tokens[:, :1])) == "jit__lambda"


def test_jitted_prefill_serves_the_eager_prefills_tokens(engine):
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, engine.cfg.vocab, size=11).tolist()
               for _ in range(2)]
    n = 5
    served = [r.tokens for r in engine.generate(prompts, max_new_tokens=n)]
    logits, cache = prefill(engine.params, engine.cfg, engine.run,
                            jnp.asarray(prompts, jnp.int32))  # eager
    cache = engine._grow_cache(cache, 11 + n)
    picked = []
    for k in range(n):
        cur = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        picked.append(np.asarray(cur))
        if k < n - 1:
            logits, cache = engine.decode(engine.params, cache, cur[:, None])
    assert served == np.stack(picked, axis=1).tolist()


def _scoped(names, scope):
    """The op_names whose path, transform wrappers stripped, holds
    ``scope``."""
    return [n for n in names
            if scope in re.sub(r"[A-Za-z_]\w*\(|\)", "", n).split("/")]


def test_train_step_is_named_and_carries_its_scopes():
    cfg = tiny_variant(get_config("mamba2-130m"))
    run = RunConfig(remat="full", zero=False, warmup_steps=2, total_steps=4)
    ctx = make_elastic_mesh_context(1)
    set_mesh_context(ctx)
    try:
        step_fn, _, _ = jit_train_step(cfg, run, ctx, 2, 64)
        state = jax.eval_shape(
            lambda: init_train_state(cfg, jax.random.PRNGKey(0)))
        batch = {k: jax.ShapeDtypeStruct((2, 64), jnp.int32)
                 for k in ("tokens", "labels")}
        text = step_fn.lower(state, batch).compile().as_text()
    finally:
        set_mesh_context(None)
    assert text.startswith("HloModule jit_train_step,")
    names = re.findall(r'op_name="([^"]*)"', text)
    ssd, head_loss = _scoped(names, "ssd"), _scoped(names, "head_loss")
    # Forward, backward (``transpose``) and, inside the remat'd layer, the
    # recomputed forward.
    assert any("transpose(" not in n for n in ssd)
    assert any("transpose(" in n for n in ssd)
    assert any("rematted_computation" in n for n in ssd)
    assert any("transpose(" not in n for n in head_loss)
    assert any("transpose(" in n for n in head_loss)
    assert not set(ssd) & set(head_loss)


def test_compile_counter_counts_a_new_program_not_a_cached_call():
    def counted_probe(x):
        return x * 3 + 1

    f = jax.jit(counted_probe)
    x = jnp.ones(3)
    before = compile_cache.compile_counts()
    f(x).block_until_ready()
    first = compile_cache.compile_counts()
    f(x).block_until_ready()
    again = compile_cache.compile_counts()

    def grew(a, b, key):
        return b.get(key, 0) - a.get(key, 0)

    assert grew(before, first, ("trace", "counted_probe")) == 1
    assert grew(before, first, ("compile", "jit(counted_probe)")) == 1
    assert again == first  # the cached call compiled nothing
    f(jnp.ones(4)).block_until_ready()  # a new shape compiles again
    assert grew(again, compile_cache.compile_counts(),
                ("compile", "jit(counted_probe)")) == 1
