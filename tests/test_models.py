"""Per-architecture smoke tests (reduced configs, CPU): one forward/train
step with shape + finiteness assertions, plus prefill/decode consistency."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import RunConfig, get_config, list_archs, tiny_variant
from repro.models import decode_step, forward_train, init_params, prefill
from repro.train import init_train_state, train_step

RUN = RunConfig(attention_impl="chunked", attention_chunk=32, remat="full",
                zero=False, warmup_steps=2, total_steps=10)
B, S = 2, 64


def make_batch(cfg, key):
    tokens = jax.random.randint(key, (B, S), 0, cfg.vocab)
    batch = {"tokens": tokens, "labels": tokens}
    if cfg.frontend != "none":
        batch["frontend"] = 0.02 * jax.random.normal(
            key, (B, cfg.frontend_len, cfg.d_model), jnp.bfloat16)
    return batch


@pytest.fixture(scope="module", params=list_archs())
def arch_setup(request):
    cfg = tiny_variant(get_config(request.param))
    key = jax.random.PRNGKey(0)
    params = init_params(cfg, key)
    return request.param, cfg, params, make_batch(cfg, key)


def test_forward_shapes_and_finite(arch_setup):
    name, cfg, params, batch = arch_setup
    hidden, extras = forward_train(params, cfg, RUN, batch["tokens"],
                                   frontend=batch.get("frontend"))
    expect_s = S + (cfg.frontend_len if cfg.frontend == "vision_stub" else 0)
    assert hidden.shape == (B, expect_s, cfg.d_model)
    assert bool(jnp.all(jnp.isfinite(hidden.astype(jnp.float32))))


def test_train_step_reduces_no_nans(arch_setup):
    name, cfg, params, batch = arch_setup
    state = init_train_state(cfg, jax.random.PRNGKey(0))
    state, metrics = train_step(state, batch, cfg, RUN)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    assert float(metrics["grad_norm"]) > 0
    assert int(state.step) == 1
    # Second step with the same data must change the loss (params moved).
    _, metrics2 = train_step(state, batch, cfg, RUN)
    assert float(metrics2["loss"]) != float(metrics["loss"])


def test_decode_matches_prefill_logits(arch_setup):
    """Teacher-forced decode: logits at position t from decode_step must
    match prefill logits of the length-(t+1) prefix."""
    name, cfg, params, batch = arch_setup
    tokens = batch["tokens"]
    frontend = batch.get("frontend")

    full_logits, _ = prefill(params, cfg, RUN, tokens, frontend=frontend)
    # Prefill on the first S-1 tokens, then decode token S-1.
    short_logits, cache = prefill(params, cfg, RUN, tokens[:, :-1],
                                  frontend=frontend)
    # Decode caches are sized by prefill length; grow for one extra token.
    from repro.serving.engine import ServeEngine
    engine = ServeEngine(cfg, params, run=RUN, batch_size=B)
    cache = engine._grow_cache(cache, tokens.shape[1] + 4, B)
    step_logits, cache2 = decode_step(params, cfg, RUN, cache, tokens[:, -1:])

    a = np.asarray(full_logits[:, -1], np.float32)
    b = np.asarray(step_logits[:, 0], np.float32)
    # bf16 compute + MoE capacity semantics (prefill routes in large groups,
    # decode in single-token groups) allow small absolute deviations; the
    # serving-level invariant is agreement of the prediction.
    np.testing.assert_allclose(a, b, rtol=0, atol=1e-1)
    assert (np.argmax(a, -1) == np.argmax(b, -1)).all()
    expected_pos = tokens.shape[1] + (
        cfg.frontend_len if cfg.frontend == "vision_stub" else 0)
    assert int(cache2["pos"]) == expected_pos


def test_serve_engine_mixed_prompt_lengths_match_forward():
    """Interleaved prompt lengths: the logits behind every generated token
    match the causal forward over the prompt plus the tokens before it, as
    if the request had been served alone."""
    from repro.models.transformer import forward_hidden, lm_logits
    from repro.serving import ServeEngine

    cfg = tiny_variant(get_config("tinyllama-1.1b"))
    params = init_params(cfg, jax.random.PRNGKey(0))
    engine = ServeEngine(cfg, params, run=RUN, batch_size=2)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, size=n).tolist()
               for n in (5, 11, 5, 11, 8)]
    results = engine.generate(prompts, max_new_tokens=4, return_logits=True)
    assert [r.request_id for r in results] == list(range(len(prompts)))
    for r in results:
        seq = jnp.asarray([r.prompt + r.tokens])
        hidden, _ = forward_hidden(params, cfg, RUN, seq)
        ref = np.asarray(lm_logits(params, cfg, hidden), np.float32)[0]
        assert r.logits.shape == (4, ref.shape[-1])
        for k, got in enumerate(r.logits):
            want = ref[len(r.prompt) + k - 1]
            err = np.linalg.norm(got - want) / np.linalg.norm(want)
            assert err < 0.05, (r.request_id, k, err)
            assert r.tokens[k] == int(np.argmax(got))


def test_serve_engine_prefill_wave_refuses_mixed_lengths():
    from repro.serving import ServeEngine

    cfg = tiny_variant(get_config("tinyllama-1.1b"))
    engine = ServeEngine(cfg, init_params(cfg, jax.random.PRNGKey(0)),
                         run=RUN, batch_size=2)
    with pytest.raises(ValueError, match="one length"):
        engine.prefill_wave([[1, 2, 3], [4, 5]], max_new_tokens=2)


def test_attention_impls_agree():
    cfg = tiny_variant(get_config("qwen3-8b"))
    params = init_params(cfg, jax.random.PRNGKey(1))
    tokens = jax.random.randint(jax.random.PRNGKey(2), (B, S), 0, cfg.vocab)
    naive = RunConfig(attention_impl="naive", remat="none", zero=False)
    chunked = RunConfig(attention_impl="chunked", attention_chunk=16,
                        remat="none", zero=False)
    h1, _ = forward_train(params, cfg, naive, tokens)
    h2, _ = forward_train(params, cfg, chunked, tokens)
    # bf16 probabilities in the PV matmul (flash-style) => bf16-level agreement.
    np.testing.assert_allclose(np.asarray(h1, np.float32),
                               np.asarray(h2, np.float32), rtol=6e-2, atol=6e-2)


def test_moe_routing_respects_topk():
    from repro.models.moe import route_topk

    g, s, e, k, cap = 2, 16, 8, 2, 8
    logits = jax.random.normal(jax.random.PRNGKey(3), (g, s, e))
    dispatch, combine, aux = route_topk(logits, k, cap)
    # Each token occupies at most top_k expert slots.
    per_token = np.asarray(jnp.sum(dispatch, axis=(2, 3)))
    assert (per_token <= k + 1e-6).all()
    # No (expert, capacity-slot) pair receives two tokens within a group.
    per_slot = np.asarray(jnp.sum(dispatch, axis=1).max())
    assert per_slot <= 1 + 1e-6
    # Combine weights are within the simplex per token.
    cw = np.asarray(jnp.sum(combine, axis=(2, 3)))
    assert (cw <= 1 + 1e-5).all()
    assert float(aux) > 0


def test_mamba_chunked_equals_stepwise():
    """SSD chunked scan == sequential single-step recurrence."""
    from repro.models.mamba2 import ssd_chunked

    key = jax.random.PRNGKey(4)
    ks = jax.random.split(key, 5)
    b, s, h, p, n, chunk = 1, 32, 2, 16, 8, 8
    x = jax.random.normal(ks[0], (b, s, h, p)) * 0.3
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    A = -jnp.exp(jax.random.normal(ks[2], (h,)) * 0.3)
    bm = jax.random.normal(ks[3], (b, s, n)) * 0.3
    cm = jax.random.normal(ks[4], (b, s, n)) * 0.3

    y_chunk, h_chunk = ssd_chunked(x, dt, A, bm, cm, chunk)

    hstate = jnp.zeros((b, h, n, p))
    ys = []
    for t in range(s):
        dA = jnp.exp(dt[:, t] * A)  # (b,h)
        xdt = x[:, t] * dt[:, t][..., None]  # (b,h,p)
        hstate = dA[..., None, None] * hstate + jnp.einsum(
            "bn,bhp->bhnp", bm[:, t], xdt)
        ys.append(jnp.einsum("bn,bhnp->bhp", cm[:, t], hstate))
    y_step = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_chunk), np.asarray(y_step),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(h_chunk), np.asarray(hstate),
                               rtol=1e-4, atol=1e-4)


def test_param_counts_match_spec():
    """Full configs land near their nameplate sizes."""
    expectations = {
        "yi-9b": (8.0e9, 9.5e9),
        "tinyllama-1.1b": (0.95e9, 1.25e9),
        "starcoder2-15b": (14e9, 17e9),
        "qwen3-8b": (7.0e9, 9.0e9),
        "deepseek-moe-16b": (14e9, 18e9),
        "phi3.5-moe-42b-a6.6b": (39e9, 45e9),
        "mamba2-130m": (0.1e9, 0.17e9),
    }
    for name, (lo, hi) in expectations.items():
        n = get_config(name).param_count()
        assert lo <= n <= hi, f"{name}: {n / 1e9:.2f}B not in [{lo / 1e9}, {hi / 1e9}]"
