"""Per-architecture smoke tests (reduced configs, CPU): one forward/train
step with shape + finiteness assertions, plus prefill/decode consistency."""

import dataclasses

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
    cache = engine._grow_cache(cache, tokens.shape[1] + 4)
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


def _xs_decode_step(params, cfg, run, cache, tokens):
    """Reference: ``decode_step`` as it was before the stacked caches were
    carried through the layer scan.  Each layer's K/V slice goes in as a
    scan input and the updated slice comes out as a scan output, and the
    block sees it as a one-layer stack."""
    from repro.models.layers import (attention_block, decode_attention,
                                     mlp_block, rms_norm, sinusoidal_positions)
    from repro.models.mamba2 import mamba_block
    from repro.models.transformer import (dense_block, embed_tokens,
                                          lm_logits, moe_layer_block)

    def one(kl, vl):
        return kl[None], vl[None]

    def unstack(kv):
        return kv[0][0], kv[1][0]

    fam, pos, b = cfg.family, cache["pos"], tokens.shape[0]
    x = embed_tokens(params, cfg, tokens)
    positions = jnp.broadcast_to(pos[None, None], (b, 1))
    new = dict(cache)
    at = dict(cache_pos=pos, cache_layer=0)
    if fam in ("dense", "moe", "audio"):
        if fam == "audio":
            x = x + jax.lax.dynamic_slice_in_dim(
                sinusoidal_positions(cache["k"].shape[2], cfg.d_model),
                pos, 1, axis=0).astype(x.dtype)[None]

        def dense_body(carry, inputs):
            lp, kl, vl = inputs
            carry, kv = dense_block(lp, carry, cfg, run, positions,
                                    kv_cache=one(kl, vl), **at)
            return carry, unstack(kv)

        if fam == "moe" and cfg.moe_first_dense:
            x, (new["dk"], new["dv"]) = jax.lax.scan(
                dense_body, x,
                (params["dense_layers"], cache["dk"], cache["dv"]))

        def body(carry, inputs):
            if fam == "moe":
                lp, kl, vl = inputs
                carry, kv, _ = moe_layer_block(lp, carry, cfg, run, positions,
                                               kv_cache=one(kl, vl), **at)
                return carry, unstack(kv)
            if fam == "audio":
                lp, kl, vl, ckl, cvl = inputs
                h, kv = attention_block(
                    lp["attn"], rms_norm(carry, lp["norm1"], cfg.norm_eps),
                    cfg, run, positions, kv_cache=one(kl, vl),
                    use_rope=False, **at)
                xx = carry + h
                cross = lp["cross"]
                q = (rms_norm(xx, lp["norm3"], cfg.norm_eps)
                     @ cross["cross_wq"]).reshape(b, 1, cfg.n_heads, cfg.d_head)
                att = decode_attention(q, ckl, cvl,
                                       jnp.full((b,), ckl.shape[1], jnp.int32))
                xx = xx + att.reshape(b, 1, -1) @ cross["cross_wo"]
                h2 = mlp_block(lp["mlp"], rms_norm(xx, lp["norm2"],
                                                   cfg.norm_eps), cfg.act)
                return xx + h2, unstack(kv)
            return dense_body(carry, inputs)

        xs = (params["layers"], cache["k"], cache["v"])
        if fam == "audio":
            xs += (cache["cross_k"], cache["cross_v"])
        x, (new["k"], new["v"]) = jax.lax.scan(body, x, xs)
    else:
        assert fam == "hybrid", fam
        x0 = x

        def mamba_body(carry, inputs):
            (c, inj), (lp, ssm, conv) = carry, inputs
            y, ssm2, conv2 = mamba_block(
                lp["mamba"], rms_norm(c + inj, lp["norm1"], cfg.norm_eps),
                cfg, ssm_state=ssm, conv_state=conv, single_step=True)
            return (c + y, jnp.zeros_like(inj)), (ssm2, conv2)

        ids = cfg.hybrid_ids
        starts = sorted({0, *ids})
        ssms, convs, ks, vs = [], [], [], []
        for first, end in zip(starts, starts[1:] + [cfg.n_layers]):
            inj = jnp.zeros_like(x)
            if first in ids:
                j = ids.index(first)
                blk = jax.tree.map(lambda a: a[j % cfg.hybrid_blocks],
                                   params["shared"])
                own = jax.tree.map(lambda a: a[j], params["invocations"])
                h, kv = attention_block(
                    blk["attn"], rms_norm(jnp.concatenate([x, x0], -1),
                                          blk["norm1"], cfg.norm_eps),
                    cfg, run, positions,
                    kv_cache=one(cache["k"][j], cache["v"][j]), **at)
                h = mlp_block(blk["mlp"], rms_norm(h, blk["norm2"],
                                                   cfg.norm_eps), cfg.act,
                              (own["adapter_down"], own["adapter_up"]))
                inj = h @ own["linear"]
                k, v = unstack(kv)
                ks.append(k)
                vs.append(v)
            run_params = jax.tree.map(lambda a: a[first:end],
                                      params["layers"])
            (x, _), (s, c) = jax.lax.scan(
                mamba_body, (x, inj),
                (run_params, cache["ssm"][first:end],
                 cache["conv"][first:end]))
            ssms.append(s)
            convs.append(c)
        new["ssm"], new["conv"] = (jnp.concatenate(ssms),
                                   jnp.concatenate(convs))
        new["k"], new["v"] = jnp.stack(ks), jnp.stack(vs)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    new["pos"] = pos + 1
    return lm_logits(params, cfg, x), new


@pytest.mark.parametrize("arch,changes", [
    ("tinyllama-1.1b", {}),
    ("deepseek-moe-16b", {"n_layers": 3}),  # 1 dense + 2 MoE layers
    ("zamba2-2.7b", {"n_layers": 4}),  # 2 invocations
    ("zamba2-7b", {"n_layers": 4}),  # the same, heads of 32 cached in 128
    ("whisper-base", {})],
    ids=["dense", "moe", "hybrid", "hybrid-wide-heads", "audio"])
def test_decode_writes_cache_in_place(arch, changes):
    """The layer scan that carries the stacked caches returns the logits and
    caches of the scan over per-layer slices, step after step, with at least
    two layers in every stack."""
    from repro.serving.engine import ServeEngine

    cfg = dataclasses.replace(tiny_variant(get_config(arch)), **changes)
    params = init_params(cfg, jax.random.PRNGKey(0))
    batch = make_batch(cfg, jax.random.PRNGKey(1))
    prompt = batch["tokens"][:, :8]
    _, cache = prefill(params, cfg, RUN, prompt,
                       frontend=batch.get("frontend"))
    engine = ServeEngine(cfg, params, run=RUN, batch_size=B)
    cache = engine._grow_cache(cache, prompt.shape[1] + 12)
    new = jax.jit(lambda c, t: decode_step(params, cfg, RUN, c, t))
    old = jax.jit(lambda c, t: _xs_decode_step(params, cfg, RUN, c, t))
    tok = prompt[:, -1:]
    for _ in range(12):
        got_logits, got = new(cache, tok)
        want_logits, want = old(cache, tok)
        np.testing.assert_array_equal(np.asarray(got_logits),
                                      np.asarray(want_logits))
        assert got.keys() == want.keys()
        for key in want:
            np.testing.assert_array_equal(np.asarray(got[key]),
                                          np.asarray(want[key]), err_msg=key)
        cache, tok = got, jnp.argmax(got_logits, -1).astype(jnp.int32)


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "zamba2-7b"])
def test_prefill_in_row_blocks_matches_prefill_at_once(arch):
    """``RunConfig.prefill_rows``: a wave prefilled a block of rows at a
    time, in one program, gives the logits and the caches of the whole
    wave prefilled at once (float32)."""
    cfg = dataclasses.replace(tiny_variant(get_config(arch)), dtype="float32")
    params = init_params(cfg, jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (6, 16), 0, cfg.vocab)
    run = dataclasses.replace(RUN, remat="none")
    whole = prefill(params, cfg, run, tokens)
    blocks = jax.jit(prefill, static_argnums=(1, 2))(
        params, cfg, dataclasses.replace(run, prefill_rows=2), tokens)
    assert jax.tree.structure(blocks) == jax.tree.structure(whole)
    for got, want in zip(jax.tree.leaves(blocks), jax.tree.leaves(whole)):
        assert got.shape == want.shape and got.dtype == want.dtype
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=0, atol=1e-5)


def test_wide_cache_heads_serve_what_narrow_heads_serve():
    """A KV cache that stores each head in more lanes than d_head, zeros
    past it (``kv_head_align``), serves the tokens of a cache of d_head
    lanes, and their logits within float32 rounding."""
    from repro.serving import ServeEngine

    base = dataclasses.replace(tiny_variant(get_config("zamba2-7b")),
                               dtype="float32")
    params = init_params(base, jax.random.PRNGKey(0))
    prompts = np.asarray(make_batch(base, jax.random.PRNGKey(1))["tokens"]
                         [:, :9]).tolist()
    served = {}
    for align in (1, 128):
        cfg = dataclasses.replace(base, kv_head_align=align)
        assert cfg.cache_head_dim == (32 if align == 1 else 128)
        served[align] = ServeEngine(cfg, params, run=RUN, batch_size=B
                                    ).generate(prompts, max_new_tokens=6,
                                               return_logits=True)
    assert [r.tokens for r in served[128]] == [r.tokens for r in served[1]]
    np.testing.assert_allclose(np.stack([r.logits for r in served[128]]),
                               np.stack([r.logits for r in served[1]]),
                               rtol=0, atol=1e-5)


def test_serve_decode_is_donated_its_cache():
    """``ServeEngine.decode`` writes the cache it is given in place: the
    cache it was given is gone after the step."""
    from repro.serving import ServeEngine

    cfg = tiny_variant(get_config("zamba2-7b"))
    engine = ServeEngine(cfg, init_params(cfg, jax.random.PRNGKey(0)),
                         run=RUN, batch_size=B)
    logits, cache = engine.prefill_wave([[1] * 8, [2] * 8], max_new_tokens=4)
    tok = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)[:, None]
    _, grown = engine.decode(engine.params, cache, tok)
    assert all(cache[key].is_deleted() for key in ("k", "v", "ssm", "conv"))
    assert int(grown["pos"]) == 9 and grown["k"].shape[2] == 12


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
