"""Model assembly for all assigned architecture families.

Layers are **stacked** (leading L dim) and iterated with ``jax.lax.scan`` so
the HLO stays compact for 512-partition SPMD compiles; remat policies wrap
the scan body.  Families:

  dense   — pre-norm GQA transformer (yi, tinyllama, starcoder2, qwen3)
  moe     — dense attention + GShard MoE FFN (deepseek-moe, phi3.5-moe),
            optional leading dense-FFN layers (DeepSeek layer 0)
  ssm     — Mamba-2 SSD stack (mamba2-130m)
  hybrid  — Mamba-2 backbone + one shared attention block every k layers
            (zamba2), concat(x, embed0) input per Zamba design
  audio   — Whisper-style encoder/decoder backbone, stub frame embeddings
  vlm     — dense backbone with stub patch embeddings prepended (phi3-vision)
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, RunConfig
from repro.distributed import constrain
from repro.models.layers import (
    DATA, MODEL, attention_block, decode_attention, mlp_block, rms_norm,
    sinusoidal_positions,
)
from repro.models.mamba2 import init_mamba_params, mamba_block
from repro.models.moe import init_moe_params, moe_block

Params = Dict[str, Any]


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


# ---------------------------------------------------------------------------
# Initialization
# ---------------------------------------------------------------------------


def _init_attn(key, cfg, layer_count, dtype, d_in=None) -> Params:
    d = d_in or cfg.d_model
    hq, hkv = cfg.n_heads * cfg.d_head, cfg.n_kv_heads * cfg.d_head
    ks = jax.random.split(key, 4)
    s = 0.02
    p = {
        "wq": jax.random.normal(ks[0], (*layer_count, d, hq), dtype) * s,
        "wk": jax.random.normal(ks[1], (*layer_count, d, hkv), dtype) * s,
        "wv": jax.random.normal(ks[2], (*layer_count, d, hkv), dtype) * s,
        "wo": jax.random.normal(ks[3], (*layer_count, hq, cfg.d_model), dtype) * s,
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((*layer_count, cfg.d_head), dtype)
        p["k_norm"] = jnp.ones((*layer_count, cfg.d_head), dtype)
    return p


def _init_mlp(key, cfg, layer_count, dtype, d_ff=None, d_in=None) -> Params:
    d = d_in or cfg.d_model
    ff = d_ff or cfg.d_ff
    width = 2 * ff if cfg.act == "swiglu" else ff
    k1, k2 = jax.random.split(key)
    s = 0.02
    return {
        "wi": jax.random.normal(k1, (*layer_count, d, width), dtype) * s,
        "wo": jax.random.normal(k2, (*layer_count, ff, cfg.d_model), dtype) * s,
    }


def _init_dense_block(key, cfg, layer_count, dtype) -> Params:
    ka, km = jax.random.split(key)
    return {
        "attn": _init_attn(ka, cfg, layer_count, dtype),
        "mlp": _init_mlp(km, cfg, layer_count, dtype),
        "norm1": jnp.ones((*layer_count, cfg.d_model), dtype),
        "norm2": jnp.ones((*layer_count, cfg.d_model), dtype),
    }


def init_params(cfg: ModelConfig, key: jax.Array) -> Params:
    dtype = _dtype(cfg)
    keys = jax.random.split(key, 8)
    params: Params = {
        "embed": jax.random.normal(
            keys[0], (cfg.padded_vocab, cfg.d_model), dtype) * 0.02,
        "final_norm": jnp.ones((cfg.d_model,), dtype),
    }
    if not cfg.tie_embeddings:
        params["lm_head"] = jax.random.normal(
            keys[1], (cfg.d_model, cfg.padded_vocab), dtype) * 0.02

    fam = cfg.family
    if fam in ("dense", "vlm"):
        params["layers"] = _init_dense_block(keys[2], cfg, (cfg.n_layers,), dtype)
    elif fam == "moe":
        n_moe = cfg.n_layers - cfg.moe_first_dense
        params["layers"] = {
            "attn": _init_attn(keys[2], cfg, (n_moe,), dtype),
            "moe": init_moe_params(keys[3], cfg, n_moe, dtype),
            "norm1": jnp.ones((n_moe, cfg.d_model), dtype),
            "norm2": jnp.ones((n_moe, cfg.d_model), dtype),
        }
        if cfg.moe_first_dense:
            params["dense_layers"] = _init_dense_block(
                keys[4], cfg, (cfg.moe_first_dense,), dtype)
    elif fam == "ssm":
        params["layers"] = {
            "mamba": init_mamba_params(keys[2], cfg, (cfg.n_layers,), dtype),
            "norm1": jnp.ones((cfg.n_layers, cfg.d_model), dtype),
        }
    elif fam == "hybrid":
        every = cfg.hybrid_attn_every
        n_groups = cfg.n_layers // every
        params["layers"] = {
            "mamba": init_mamba_params(keys[2], cfg, (n_groups, every), dtype),
            "norm1": jnp.ones((n_groups, every, cfg.d_model), dtype),
        }
        d2 = 2 * cfg.d_model
        params["shared_attn"] = _init_attn(keys[3], cfg, (), dtype, d_in=d2)
        params["shared_mlp"] = _init_mlp(keys[4], cfg, (), dtype, d_in=d2)
        params["shared_norm1"] = jnp.ones((d2,), dtype)
        params["shared_norm2"] = jnp.ones((d2,), dtype)
        params["inv_proj"] = jax.random.normal(
            keys[5], (n_groups, cfg.d_model, cfg.d_model), dtype) * 0.02
    elif fam == "audio":
        params["enc_layers"] = _init_dense_block(
            keys[2], cfg, (cfg.n_encoder_layers,), dtype)
        dec = _init_dense_block(keys[3], cfg, (cfg.n_layers,), dtype)
        ca = _init_attn(keys[4], cfg, (cfg.n_layers,), dtype)
        dec["cross"] = {"cross_wq": ca["wq"], "cross_wk": ca["wk"],
                        "cross_wv": ca["wv"], "cross_wo": ca["wo"]}
        dec["norm3"] = jnp.ones((cfg.n_layers, cfg.d_model), dtype)
        params["layers"] = dec
        params["enc_final_norm"] = jnp.ones((cfg.d_model,), dtype)
    else:  # pragma: no cover
        raise ValueError(f"unknown family {fam}")
    return params


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------


def _remat(fn, run: RunConfig):
    if run.remat == "none":
        return fn
    if run.remat == "dots":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.dots_saveable)
    return jax.checkpoint(fn)  # "full"/"coarse": nothing saveable


def _seq_constrain(x, run: RunConfig):
    """Sequence-parallel residual stream (Megatron-SP via GSPMD)."""
    if run.seq_shard:
        return constrain(x, DATA, MODEL, None)
    return constrain(x, DATA, None, None)


def dense_block(lp, x, cfg, run, positions, causal=True, use_rope=True,
                kv_cache=None, cache_pos=None, cache_layer=None, enc_out=None):
    """One pre-norm transformer block (+ optional cross-attention)."""
    h, kv = attention_block(lp["attn"], rms_norm(x, lp["norm1"], cfg.norm_eps),
                            cfg, run, positions, kv_cache=kv_cache,
                            cache_pos=cache_pos, cache_layer=cache_layer,
                            causal=causal, use_rope=use_rope)
    x = _seq_constrain(x + h, run)
    if enc_out is not None:
        cross = lp["cross"]
        cp = {"wq": cross["cross_wq"], "wk": cross["cross_wk"],
              "wv": cross["cross_wv"], "wo": cross["cross_wo"]}
        h, _ = attention_block(cp, rms_norm(x, lp["norm3"], cfg.norm_eps),
                               cfg, run, positions, kv_x=enc_out,
                               causal=False, use_rope=False)
        x = _seq_constrain(x + h, run)
    h = mlp_block(lp["mlp"], rms_norm(x, lp["norm2"], cfg.norm_eps), cfg.act)
    return _seq_constrain(x + h, run), kv


def moe_layer_block(lp, x, cfg, run, positions, kv_cache=None, cache_pos=None,
                    cache_layer=None):
    h, kv = attention_block(lp["attn"], rms_norm(x, lp["norm1"], cfg.norm_eps),
                            cfg, run, positions, kv_cache=kv_cache,
                            cache_pos=cache_pos, cache_layer=cache_layer)
    x = _seq_constrain(x + h, run)
    h, aux = moe_block(lp["moe"], rms_norm(x, lp["norm2"], cfg.norm_eps), cfg,
                       dispatch_mode=run.moe_dispatch)
    return _seq_constrain(x + h, run), kv, aux


def hybrid_shared_block(params, x, x0, inv_proj, cfg, run, positions,
                        kv_cache=None, cache_pos=None, cache_fill=None,
                        cache_layer=None):
    """Zamba2 shared attention block on concat(x, embed0)."""
    xin = jnp.concatenate([x, x0], axis=-1)
    h, kv = attention_block(params["shared_attn"],
                            rms_norm(xin, params["shared_norm1"], cfg.norm_eps),
                            cfg, run, positions, kv_cache=kv_cache,
                            cache_pos=cache_pos, cache_fill=cache_fill,
                            cache_layer=cache_layer)
    m = mlp_block(params["shared_mlp"],
                  rms_norm(xin, params["shared_norm2"], cfg.norm_eps), cfg.act)
    return _seq_constrain(x + (h + m) @ inv_proj, run), kv


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------


def embed_tokens(params, cfg, tokens):
    x = jnp.take(params["embed"], tokens, axis=0)
    return constrain(x, DATA, None, None)


def lm_logits(params, cfg, x):
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    logits = x.astype(jnp.float32) @ head.astype(jnp.float32)
    logits = constrain(logits, DATA, None, MODEL)
    if cfg.padded_vocab != cfg.vocab:  # mask vocabulary padding
        cols = jnp.arange(cfg.padded_vocab)
        logits = jnp.where(cols[None, None, :] < cfg.vocab, logits, -1e30)
    return logits


# ---------------------------------------------------------------------------
# Forward (train / prefill): returns hidden states (+ caches when requested)
# ---------------------------------------------------------------------------


def _stack_scan(body, x, stacked, run: RunConfig, collect=False):
    wrapped = _remat(body, run)

    def f(carry, lp):
        new, out = wrapped(carry, lp)
        return new, (out if collect else None)

    x, ys = jax.lax.scan(f, x, stacked)
    return x, ys


def forward_hidden(
    params: Params, cfg: ModelConfig, run: RunConfig,
    tokens: jnp.ndarray,
    frontend: Optional[jnp.ndarray] = None,
    collect_kv: bool = False,
) -> Tuple[jnp.ndarray, Dict[str, Any]]:
    """Token (+frontend) embeddings through the stack.

    Returns (hidden (B,S,d), extras{aux_loss, kv/ssm caches, enc_out}).
    """
    extras: Dict[str, Any] = {"aux": jnp.zeros((), jnp.float32)}
    fam = cfg.family

    x = embed_tokens(params, cfg, tokens)
    if fam == "vlm" and frontend is not None:
        x = jnp.concatenate([frontend.astype(x.dtype), x], axis=1)
    x = _seq_constrain(x, run)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))

    if fam in ("dense", "vlm"):
        def body(carry, lp):
            new, kv = dense_block(lp, carry, cfg, run, positions)
            return new, (kv if collect_kv else 0)
        x, kvs = _stack_scan(body, x, params["layers"], run, collect=collect_kv)
        if collect_kv:
            extras["kv"] = kvs

    elif fam == "moe":
        if cfg.moe_first_dense:
            def dbody(carry, lp):
                new, kv = dense_block(lp, carry, cfg, run, positions)
                return new, (kv if collect_kv else 0)
            x, dkvs = _stack_scan(dbody, x, params["dense_layers"], run,
                                  collect=collect_kv)
            if collect_kv:
                extras["dense_kv"] = dkvs

        def body(carry, lp):
            new, kv, aux = moe_layer_block(lp, carry, cfg, run, positions)
            return new, ((kv, aux) if collect_kv else aux)
        x, ys = _stack_scan(body, x, params["layers"], run, collect=True)
        if collect_kv:
            extras["kv"], aux = ys
        else:
            aux = ys
        extras["aux"] = jnp.mean(aux)

    elif fam == "ssm":
        def body(carry, lp):
            h = rms_norm(carry, lp["norm1"], cfg.norm_eps)
            y, ssm, conv = mamba_block(lp["mamba"], h, cfg,
                                       chunk_shard=run.ssd_chunk_shard)
            return _seq_constrain(carry + y, run), \
                ((ssm, conv) if collect_kv else 0)
        if run.remat != "none":
            body = jax.checkpoint(body)  # nested: SSD residuals recomputed
        x, states = _stack_scan(body, x, params["layers"], run, collect=collect_kv)
        if collect_kv:
            extras["ssm"] = states

    elif fam == "hybrid":
        x0 = x
        n_groups = cfg.n_layers // cfg.hybrid_attn_every

        def group_body(xg, lp):
            def inner(c, lpi):
                h = rms_norm(c, lpi["norm1"], cfg.norm_eps)
                y, ssm, conv = mamba_block(lpi["mamba"], h, cfg,
                                           chunk_shard=run.ssd_chunk_shard)
                return _seq_constrain(c + y, run), ((ssm, conv) if collect_kv else 0)

            if run.remat != "none":
                inner = jax.checkpoint(inner)  # nested: per-layer SSD remat
            xg, states = jax.lax.scan(
                inner, xg,
                {"mamba": lp["mamba"], "norm1": lp["norm1"]})
            xg, kv = hybrid_shared_block(params, xg, x0, lp["inv_proj"],
                                         cfg, run, positions)
            out = (states, kv) if collect_kv else 0
            return xg, out

        stacked = {"mamba": params["layers"]["mamba"],
                   "norm1": params["layers"]["norm1"],
                   "inv_proj": params["inv_proj"]}
        wrapped = _remat(group_body, run)
        x, ys = jax.lax.scan(wrapped, x, stacked)
        if collect_kv:
            extras["ssm"], extras["kv"] = ys

    elif fam == "audio":
        # Encoder over stub frame embeddings.
        enc = frontend.astype(x.dtype)
        enc = enc + sinusoidal_positions(enc.shape[1], cfg.d_model).astype(enc.dtype)
        enc = _seq_constrain(enc, run)
        epos = jnp.broadcast_to(jnp.arange(enc.shape[1])[None], enc.shape[:2])

        def ebody(carry, lp):
            new, _ = dense_block(lp, carry, cfg, run, epos, causal=False,
                                 use_rope=False)
            return new, None
        enc, _ = _stack_scan(ebody, enc, params["enc_layers"], run)
        enc = rms_norm(enc, params["enc_final_norm"], cfg.norm_eps)
        extras["enc_out"] = enc

        x = x + sinusoidal_positions(s, cfg.d_model).astype(x.dtype)

        def dbody(carry, lp):
            new, kv = dense_block(lp, carry, cfg, run, positions,
                                  use_rope=False, enc_out=enc)
            return new, (kv if collect_kv else 0)
        x, kvs = _stack_scan(dbody, x, params["layers"], run, collect=collect_kv)
        if collect_kv:
            extras["kv"] = kvs
    else:  # pragma: no cover
        raise ValueError(fam)

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, extras


def forward_train(params, cfg, run, tokens, frontend=None):
    """Hidden states for training (logits computed by the loss, which may
    chunk over the sequence to avoid materializing (B,S,V))."""
    return forward_hidden(params, cfg, run, tokens, frontend, collect_kv=False)


# ---------------------------------------------------------------------------
# Serving: cache init / prefill / decode
# ---------------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Params:
    """Abstract-friendly cache pytree (zeros; dryrun passes ShapeDtypeStructs)."""
    dtype = _dtype(cfg)
    hkv, dh = cfg.n_kv_heads, cfg.d_head
    cache: Params = {"pos": jnp.zeros((), jnp.int32)}
    fam = cfg.family

    def kv(layer_count, length):
        return (jnp.zeros((layer_count, batch, length, hkv, dh), dtype),
                jnp.zeros((layer_count, batch, length, hkv, dh), dtype))

    if fam in ("dense", "vlm"):
        cache["k"], cache["v"] = kv(cfg.n_layers, max_len)
    elif fam == "moe":
        n_moe = cfg.n_layers - cfg.moe_first_dense
        cache["k"], cache["v"] = kv(n_moe, max_len)
        if cfg.moe_first_dense:
            cache["dk"], cache["dv"] = kv(cfg.moe_first_dense, max_len)
    elif fam == "ssm":
        di, n, nh, p = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
        conv_ch = di + 2 * n
        cache["ssm"] = jnp.zeros((cfg.n_layers, batch, nh, n, p), dtype)
        cache["conv"] = jnp.zeros((cfg.n_layers, batch, cfg.ssm_conv - 1, conv_ch), dtype)
    elif fam == "hybrid":
        every = cfg.hybrid_attn_every
        n_groups = cfg.n_layers // every
        di, n, nh, p = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
        conv_ch = di + 2 * n
        cache["ssm"] = jnp.zeros((n_groups, every, batch, nh, n, p), dtype)
        cache["conv"] = jnp.zeros((n_groups, every, batch, cfg.ssm_conv - 1, conv_ch), dtype)
        wlen = min(cfg.window or max_len, max_len)
        cache["k"], cache["v"] = kv(n_groups, wlen)
    elif fam == "audio":
        cache["k"], cache["v"] = kv(cfg.n_layers, max_len)
        f = cfg.frontend_len
        cache["cross_k"], cache["cross_v"] = kv(cfg.n_layers, f)
    return cache


def prefill(params, cfg, run, tokens, frontend=None):
    """Full-sequence forward that also returns the populated KV caches."""
    hidden, extras = forward_hidden(params, cfg, run, tokens, frontend,
                                    collect_kv=True)
    logits_last = lm_logits(params, cfg, hidden[:, -1:])
    b = tokens.shape[0]
    s = hidden.shape[1]
    cache = init_cache(cfg, b, s)
    if "kv" in extras:
        k, v = extras["kv"]  # (L, B, S, K, D)
        if cfg.family == "hybrid":
            w = cache["k"].shape[2]
            k, v = k[:, :, -w:], v[:, :, -w:]
        cache["k"] = k.astype(cache["k"].dtype)
        cache["v"] = v.astype(cache["v"].dtype)
    if "dense_kv" in extras:
        dk, dv = extras["dense_kv"]
        cache["dk"] = dk.astype(cache["dk"].dtype)
        cache["dv"] = dv.astype(cache["dv"].dtype)
    if "ssm" in extras:
        ssm, conv = extras["ssm"]
        cache["ssm"] = ssm.astype(cache["ssm"].dtype)
        cache["conv"] = conv.astype(cache["conv"].dtype)
    if "enc_out" in extras:  # whisper: precompute cross KV per layer
        enc = extras["enc_out"]
        ca = params["layers"]["cross"]
        b_, f, _ = enc.shape
        ck = jnp.einsum("bfd,ldh->lbfh", enc, ca["cross_wk"])
        cv = jnp.einsum("bfd,ldh->lbfh", enc, ca["cross_wv"])
        hkv, dh = cfg.n_kv_heads, cfg.d_head
        cache["cross_k"] = ck.reshape(cfg.n_layers, b_, f, hkv, dh).astype(
            cache["cross_k"].dtype)
        cache["cross_v"] = cv.reshape(cfg.n_layers, b_, f, hkv, dh).astype(
            cache["cross_v"].dtype)
    cache["pos"] = jnp.asarray(s, jnp.int32)
    return logits_last, cache


def _scan_with_cache(body, x, xs, k, v):
    """Scan ``body(x, xs_l, layer, k, v) -> (x, (k, v), y)`` over the layer
    stack, carrying the stacked caches and the layer index: each layer
    writes and reads its KV in place, and no layer's cache is sliced out or
    stacked back.  Returns (x, k, v, stacked ys)."""
    def step(carry, xs_l):
        x, layer, k, v = carry
        x, (k, v), y = body(x, xs_l, layer, k, v)
        return (x, layer + 1, k, v), y

    (x, _, k, v), ys = jax.lax.scan(
        step, (x, jnp.zeros((), jnp.int32), k, v), xs)
    return x, k, v, ys


def decode_step(params, cfg, run, cache, tokens):
    """One decode step: tokens (B,1) + cache -> (logits (B,1,V), new cache).

    The KV/state update chain is the loop-carried dependency the serve loop's
    LCD analysis reports.
    """
    fam = cfg.family
    pos = cache["pos"]
    b = tokens.shape[0]
    x = embed_tokens(params, cfg, tokens)
    positions = jnp.broadcast_to(pos[None, None], (b, 1))
    new_cache = dict(cache)

    if fam in ("dense", "vlm", "moe", "audio"):
        if fam == "audio":
            x = x + jax.lax.dynamic_slice_in_dim(
                sinusoidal_positions(cache["k"].shape[2], cfg.d_model),
                pos, 1, axis=0).astype(x.dtype)[None]

        def dense_body(carry, lp, layer, k, v):
            return *dense_block(lp, carry, cfg, run, positions, kv_cache=(k, v),
                                cache_pos=pos, cache_layer=layer), None

        if fam == "moe" and cfg.moe_first_dense:
            x, new_cache["dk"], new_cache["dv"], _ = _scan_with_cache(
                dense_body, x, params["dense_layers"], cache["dk"],
                cache["dv"])

        def body(carry, inputs, layer, k, v):
            if fam == "moe":
                new, kv, _aux = moe_layer_block(
                    inputs, carry, cfg, run, positions, kv_cache=(k, v),
                    cache_pos=pos, cache_layer=layer)
                return new, kv, None
            if fam == "audio":
                lp, ckl, cvl = inputs
                h, kv = attention_block(
                    lp["attn"], rms_norm(carry, lp["norm1"], cfg.norm_eps),
                    cfg, run, positions, kv_cache=(k, v), cache_pos=pos,
                    cache_layer=layer, use_rope=False)
                xx = carry + h
                cp = {"wq": lp["cross"]["cross_wq"], "wk": lp["cross"]["cross_wk"],
                      "wv": lp["cross"]["cross_wv"], "wo": lp["cross"]["cross_wo"]}
                q = (rms_norm(xx, lp["norm3"], cfg.norm_eps) @ cp["wq"]).reshape(
                    b, 1, cfg.n_heads, cfg.d_head)
                f = ckl.shape[1]
                att = decode_attention(q, ckl, cvl,
                                       jnp.full((b,), f, jnp.int32))
                xx = xx + att.reshape(b, 1, -1) @ cp["wo"]
                h2 = mlp_block(lp["mlp"], rms_norm(xx, lp["norm2"], cfg.norm_eps),
                               cfg.act)
                return xx + h2, kv, None
            return dense_body(carry, inputs, layer, k, v)

        if fam == "audio":
            xs = (params["layers"], cache["cross_k"], cache["cross_v"])
        else:
            xs = params["layers"]
        x, new_cache["k"], new_cache["v"], _ = _scan_with_cache(
            body, x, xs, cache["k"], cache["v"])

    elif fam == "ssm":
        def body(carry, inputs):
            lp, ssm, conv = inputs
            h = rms_norm(carry, lp["norm1"], cfg.norm_eps)
            y, ssm2, conv2 = mamba_block(lp["mamba"], h, cfg, ssm_state=ssm,
                                         conv_state=conv, single_step=True)
            return carry + y, (ssm2, conv2)
        x, (ssm, conv) = jax.lax.scan(
            body, x, (params["layers"], cache["ssm"], cache["conv"]))
        new_cache["ssm"], new_cache["conv"] = ssm, conv

    elif fam == "hybrid":
        x0 = x
        wlen = cache["k"].shape[2]
        slot = jnp.mod(pos, wlen)

        def group_body(xg, inputs, group, k, v):
            lp, ssm_g, conv_g = inputs

            def inner(c, xs_inner):
                lpi, ssm, conv = xs_inner
                h = rms_norm(c, lpi["norm1"], cfg.norm_eps)
                y, ssm2, conv2 = mamba_block(lpi["mamba"], h, cfg,
                                             ssm_state=ssm, conv_state=conv,
                                             single_step=True)
                return c + y, (ssm2, conv2)

            xg, states = jax.lax.scan(
                inner, xg,
                ({"mamba": lp["mamba"], "norm1": lp["norm1"]}, ssm_g, conv_g))
            xg, kv = hybrid_shared_block(
                params, xg, x0, lp["inv_proj"], cfg, run, positions,
                kv_cache=(k, v), cache_pos=slot,
                cache_fill=jnp.minimum(pos + 1, wlen), cache_layer=group)
            return xg, kv, states

        stacked = ({"mamba": params["layers"]["mamba"],
                    "norm1": params["layers"]["norm1"],
                    "inv_proj": params["inv_proj"]},
                   cache["ssm"], cache["conv"])
        x, k, v, (ssm, conv) = _scan_with_cache(
            group_body, x, stacked, cache["k"], cache["v"])
        new_cache.update({"ssm": ssm, "conv": conv, "k": k, "v": v})

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = lm_logits(params, cfg, x)
    new_cache["pos"] = pos + 1
    return logits, new_cache
