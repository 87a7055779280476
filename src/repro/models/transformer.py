"""Model assembly for all assigned architecture families.

Layers are **stacked** (leading L dim) and iterated with ``jax.lax.scan`` so
the HLO stays compact for 512-partition SPMD compiles; remat policies wrap
the scan body.  Families:

  dense   — pre-norm GQA transformer (yi, tinyllama, starcoder2, qwen3)
  moe     — dense attention + GShard MoE FFN (deepseek-moe, phi3.5-moe),
            optional leading dense-FFN layers (DeepSeek layer 0)
  ssm     — Mamba-2 SSD stack (mamba2-130m)
  hybrid  — Zamba2: a Mamba-2 stack with shared attention blocks, used in
            turn, before the layers ``hybrid_pattern`` marks; each block
            reads concat(x, embed0) and feeds that Mamba-2 layer's input
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
    width = 2 * ff if cfg.act in ("swiglu", "geglu") else ff
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
        d, nb = cfg.d_model, cfg.hybrid_blocks
        n_inv, r = len(cfg.hybrid_ids), cfg.adapter_rank
        params["layers"] = {
            "mamba": init_mamba_params(keys[2], cfg, (cfg.n_layers,), dtype),
            "norm1": jnp.ones((cfg.n_layers, d), dtype),
        }
        params["shared"] = {
            "attn": _init_attn(keys[3], cfg, (nb,), dtype, d_in=2 * d),
            "mlp": _init_mlp(keys[4], cfg, (nb,), dtype),
            "norm1": jnp.ones((nb, 2 * d), dtype),
            "norm2": jnp.ones((nb, d), dtype),
        }
        ks = jax.random.split(keys[5], 3)
        inv = {"linear": jax.random.normal(ks[0], (n_inv, d, d), dtype) * 0.02}
        if r:
            width = params["shared"]["mlp"]["wi"].shape[-1]
            inv["adapter_down"] = jax.random.normal(
                ks[1], (n_inv, d, r), dtype) * 0.02
            inv["adapter_up"] = jax.random.normal(
                ks[2], (n_inv, r, width), dtype) * 0.02
        params["invocations"] = inv
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


def hybrid_shared_block(params, x, x0, inv: int, cfg, run, positions,
                        kv_cache=None, cache_pos=None):
    """Zamba2's shared block at invocation ``inv``: block ``inv %
    hybrid_blocks`` on concat(x, embed0), attention then the MLP with the
    invocation's adapter (no residual inside), then the invocation's linear
    map.  Returns what is added to the next Mamba-2 layer's input, and the
    KV (decode: the stack, written at layer ``inv``)."""
    blk = jax.tree.map(lambda a: a[inv % cfg.hybrid_blocks], params["shared"])
    own = jax.tree.map(lambda a: a[inv], params["invocations"])
    h = rms_norm(jnp.concatenate([x, x0], axis=-1), blk["norm1"],
                 cfg.norm_eps)
    h, kv = attention_block(blk["attn"], h, cfg, run, positions,
                            kv_cache=kv_cache, cache_pos=cache_pos,
                            cache_layer=jnp.asarray(inv, jnp.int32))
    adapter = (own["adapter_down"], own["adapter_up"]) \
        if "adapter_down" in own else None
    h = mlp_block(blk["mlp"], rms_norm(h, blk["norm2"], cfg.norm_eps),
                  cfg.act, adapter)
    return h @ own["linear"], kv


def hybrid_runs(cfg) -> list:
    """``(invocation or None, first layer, end layer)`` of each run of
    Mamba-2 layers: runs start at layer 0 and at each shared block's layer,
    whose invocation feeds the run's first layer."""
    ids = cfg.hybrid_ids
    starts = sorted({0, *ids})
    return [(ids.index(s) if s in ids else None, s, e)
            for s, e in zip(starts, starts[1:] + [cfg.n_layers])]


def mamba_run(layers, x, inject, first, end, cfg, run, states=None,
              collect=False):
    """Mamba-2 layers ``first..end-1`` of the stack ``layers`` (indexed in
    place, not sliced out), pre-norm, residual; ``inject`` (or None) is
    added to the first layer's input only.

    ``states``: decode's (ssm, conv) stacks of every layer, read and
    written in place at each layer's index; returns (x, states).  Else
    (prefill/train) returns (x, each layer's (ssm, conv) stacked if
    ``collect``)."""
    def at(a, i):
        return jax.lax.dynamic_index_in_dim(a, i, keepdims=False)

    def body(carry, i):
        x, inj, st = carry
        lp = jax.tree.map(lambda a: at(a, i), layers)
        h = rms_norm(x if inj is None else x + inj, lp["norm1"], cfg.norm_eps)
        if st is None:
            y, ssm, conv = mamba_block(lp["mamba"], h, cfg,
                                       chunk_shard=run.ssd_chunk_shard)
            out = (ssm, conv) if collect else 0
        else:
            y, ssm, conv = mamba_block(
                lp["mamba"], h, cfg, ssm_state=at(st[0], i),
                conv_state=at(st[1], i), single_step=True)
            st = tuple(jax.lax.dynamic_update_index_in_dim(s, new, i, 0)
                       for s, new in zip(st, (ssm, conv)))
            out = 0
        inj = None if inj is None else jnp.zeros_like(inj)
        return (_seq_constrain(x + y, run), inj, st), out

    if states is None and run.remat != "none":
        body = jax.checkpoint(body)  # per-layer SSD remat
    (x, _, states), ys = jax.lax.scan(
        body, (x, inject, states), jnp.arange(first, end))
    return x, (states if states is not None else ys)


def hybrid_stack(params, x, cfg, run, positions, cache=None, collect=False):
    """The hybrid family's layers over embeddings ``x``, run by run
    (``hybrid_runs``), each shared block under the named scope
    ``shared_block`` and each run of Mamba-2 layers under ``mamba``.

    Prefill/train (``cache`` None): returns (x, extras) with, if
    ``collect``, ``kv`` (K, V stacked by invocation) and ``ssm`` (ssm and
    conv states stacked by layer).  Decode: ``cache`` holds those stacks
    and the position; returns (x, their updates)."""
    x0, kvs, states, decode = x, [], [], cache is not None
    if decode:
        k, v, st = cache["k"], cache["v"], (cache["ssm"], cache["conv"])
    for inv, first, end in hybrid_runs(cfg):
        inject = None
        if inv is not None:
            with jax.named_scope("shared_block"):
                if decode:
                    inject, (k, v) = hybrid_shared_block(
                        params, x, x0, inv, cfg, run, positions,
                        kv_cache=(k, v), cache_pos=cache["pos"])
                else:
                    inject, kv = hybrid_shared_block(params, x, x0, inv, cfg,
                                                     run, positions)
                    kvs.append(kv)
        with jax.named_scope("mamba"):
            x, out = mamba_run(params["layers"], x, inject, first, end, cfg,
                               run, st if decode else None, collect)
        if decode:
            st = out
        elif collect:
            states.append(out)
    if decode:
        return x, {"k": k, "v": v, "ssm": st[0], "conv": st[1]}
    extras = {}
    if collect:
        extras["kv"] = tuple(jnp.stack(t) for t in zip(*kvs))
        extras["ssm"] = tuple(jnp.concatenate(t) for t in zip(*states))
    return x, extras


def _cache_heads(kv, stack):
    """(..., K, D) K or V in a cache stack's dtype, zeros past D up to the
    stack's head width (``kv_head_align``)."""
    wide = stack.shape[-1] - kv.shape[-1]
    if wide:
        kv = jnp.pad(kv, ((0, 0),) * (kv.ndim - 1) + ((0, wide),))
    return kv.astype(stack.dtype)


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
        x, states = hybrid_stack(params, x, cfg, run, positions,
                                 collect=collect_kv)
        extras.update(states)

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
    hkv = cfg.n_kv_heads
    cache: Params = {"pos": jnp.zeros((), jnp.int32)}
    fam = cfg.family

    def kv(layer_count, length, dh=cfg.cache_head_dim):
        return (jnp.zeros((layer_count, batch, length, hkv, dh), dtype),
                jnp.zeros((layer_count, batch, length, hkv, dh), dtype))

    if fam in ("dense", "vlm"):
        cache["k"], cache["v"] = kv(cfg.n_layers, max_len)
    elif fam == "moe":
        n_moe = cfg.n_layers - cfg.moe_first_dense
        cache["k"], cache["v"] = kv(n_moe, max_len)
        if cfg.moe_first_dense:
            cache["dk"], cache["dv"] = kv(cfg.moe_first_dense, max_len)
    elif fam in ("ssm", "hybrid"):
        nh, n, p = cfg.ssm_heads, cfg.ssm_state, cfg.ssm_head_dim
        conv_ch = cfg.d_inner + 2 * cfg.ssm_groups * n
        cache["ssm"] = jnp.zeros((cfg.n_layers, batch, nh, n, p), dtype)
        cache["conv"] = jnp.zeros(
            (cfg.n_layers, batch, cfg.ssm_conv - 1, conv_ch), dtype)
        if fam == "hybrid":  # by invocation
            cache["k"], cache["v"] = kv(len(cfg.hybrid_ids), max_len)
    elif fam == "audio":
        cache["k"], cache["v"] = kv(cfg.n_layers, max_len)
        f = cfg.frontend_len
        cache["cross_k"], cache["cross_v"] = kv(cfg.n_layers, f, cfg.d_head)
    return cache


def prefill(params, cfg, run, tokens, frontend=None):
    """Full-sequence forward that also returns the populated KV caches.
    With ``run.prefill_rows`` (and no frontend), the rows go through it a
    block at a time, each block's logits and caches written into the
    whole wave's."""
    b, rows = tokens.shape[0], run.prefill_rows
    if not rows or rows >= b or frontend is not None:
        return _prefill(params, cfg, run, tokens, frontend)
    assert b % rows == 0, f"{b} rows do not split into blocks of {rows}"

    def block(i, done):
        logits, cache = done
        start = i * rows
        part_logits, part = _prefill(
            params, cfg, run, jax.lax.dynamic_slice_in_dim(tokens, start, rows))
        logits = jax.lax.dynamic_update_slice_in_dim(logits, part_logits,
                                                     start, axis=0)
        return logits, {  # every cache leaf but the position is (L, B, ...)
            key: a if key == "pos" else jax.lax.dynamic_update_slice_in_dim(
                a, part[key], start, axis=1)
            for key, a in cache.items()}

    cache = init_cache(cfg, b, tokens.shape[1])
    cache["pos"] = jnp.asarray(tokens.shape[1], jnp.int32)
    logits = jnp.zeros((b, 1, cfg.padded_vocab), jnp.float32)
    return jax.lax.fori_loop(0, b // rows, block, (logits, cache))


def _prefill(params, cfg, run, tokens, frontend=None):
    hidden, extras = forward_hidden(params, cfg, run, tokens, frontend,
                                    collect_kv=True)
    logits_last = lm_logits(params, cfg, hidden[:, -1:])
    b = tokens.shape[0]
    s = hidden.shape[1]
    cache = init_cache(cfg, b, s)
    for src, names in (("kv", ("k", "v")), ("dense_kv", ("dk", "dv"))):
        for name, c in zip(names, extras.get(src, ())):  # (L, B, S, K, D)
            cache[name] = _cache_heads(c, cache[name])
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
        x, states = hybrid_stack(params, x, cfg, run, positions, cache=cache)
        new_cache.update(states)

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits = lm_logits(params, cfg, x)
    new_cache["pos"] = pos + 1
    return logits, new_cache
