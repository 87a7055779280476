"""The dense family: a Llama-architecture decoder (Yi: arXiv:2403.04652).

What the harness knows of the family: its parameter leaves (``leaves``),
its counts over the stack (``stack_params`` ... ``decode_state_bytes``,
which ``flops.py`` adds to the embedding and head), its float32 reference
(``logits``) and its CPU sizes (``TINY``).

Pre-norm blocks: RMSNorm, grouped-query attention with rotary position
embedding (the rotate-half form: the first and second halves of each head
are the pair), SwiGLU MLP (``wi`` holds the gate's columns, then the up
projection's), a final RMSNorm and an untied output head.  Query head ``h``
reads key/value head ``h // (n_heads / n_kv_heads)``.

It runs layer by layer on the device, drawing each layer's weights from the
seed (``weights.layer_params``) as the served dtype and computing in float32
at ``Precision.HIGHEST``, so it holds one layer at a time.  Attention is
computed whole (causal mask, then softmax), not chunked, and without any
cache.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Mapping

import jax
import jax.numpy as jnp

from benchmarks.chip import weights
from benchmarks.chip.reference.numerics import (
    einsum, masked_logits, matmul, rms_norm, silu)
from benchmarks.chip.weights import Leaf

# The query and key projections are drawn N(0, QK_SPREAD / d_model), so that
# attention scores spread by ~QK_SPREAD as a trained model's heads do (at
# 0.02 they spread by 0.02^2 d_model, 1.6 at Yi's width, and attention over
# a long prompt is nearly uniform: a decode that read a stale cache would
# then give nearly the same logits).
QK_SPREAD = 4.0  # standard deviation of the attention scores q.k / sqrt(d)

# Widths and lengths small enough for the CPU rehearsal.
TINY = {"n_layers": 2, "d_model": 512, "n_heads": 4, "n_kv_heads": 2,
        "d_head": 128, "d_ff": 1024, "vocab": 1000}


def leaves(m: Mapping) -> dict:
    """Each layer's leaves, stacked ``n_layers`` deep."""
    d, ff = m["d_model"], m["d_ff"]
    hq, hkv = m["n_heads"] * m["d_head"], m["n_kv_heads"] * m["d_head"]
    qk = ("normal", (QK_SPREAD / d) ** 0.5)
    return {
        ("layers", "attn", "wq"): Leaf((d, hq), qk),
        ("layers", "attn", "wk"): Leaf((d, hkv), qk),
        ("layers", "attn", "wv"): Leaf((d, hkv), ("normal", 0.02)),
        ("layers", "attn", "wo"): Leaf((hq, d), ("normal", 0.02)),
        ("layers", "mlp", "wi"): Leaf((d, 2 * ff), ("normal", 0.02)),
        ("layers", "mlp", "wo"): Leaf((ff, d), ("normal", 0.02)),
        ("layers", "norm1"): Leaf((d,), ("scale",)),
        ("layers", "norm2"): Leaf((d,), ("scale",)),
    }


def _layer_params(m: Mapping) -> int:
    d = m["d_model"]
    hq = m["n_heads"] * m["d_head"]
    hkv = m["n_kv_heads"] * m["d_head"]
    attn = d * hq + 2 * d * hkv + hq * d
    mlp = 3 * d * m["d_ff"]  # SwiGLU: gate, up, down
    return attn + mlp + 2 * d  # + two RMSNorm scales


def stack_params(m: Mapping) -> int:
    """Parameters of every layer."""
    return m["n_layers"] * _layer_params(m)


def matmul_params(m: Mapping) -> int:
    """Parameters a token reads in matrix products, every layer: all but
    the norms' scales."""
    return m["n_layers"] * (_layer_params(m) - 2 * m["d_model"])


def mixer_flops(m: Mapping, keys: float) -> float:
    """Attention's scores and weighted sum of one query over ``keys`` keys,
    all layers."""
    hq = m["n_heads"] * m["d_head"]
    return m["n_layers"] * 4.0 * hq * keys


def decode_mixer_flops(m: Mapping, cache_len: int) -> float:
    """A decode row's attention over ``cache_len`` positions (its own
    included), all layers."""
    return mixer_flops(m, cache_len)


def decode_state_bytes(m: Mapping, cache_len: int, cache_bytes: int) -> int:
    """A decode row's KV cache traffic, all layers: the valid prefix
    (``cache_len - 1`` positions) read and the new position written."""
    per_pos = 2 * m["n_layers"] * m["n_kv_heads"] * m["d_head"] * cache_bytes
    return per_pos * cache_len


def rope(x, theta):
    """x: (B, S, H, D) at positions 0..S-1."""
    s, d = x.shape[1], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angle)[None, :, None], jnp.sin(angle)[None, :, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def layer(lp, x, m: Mapping, precision: str = "f32"):
    b, s, _ = x.shape
    h_q, h_kv, dh = m["n_heads"], m["n_kv_heads"], m["d_head"]
    eps = m["norm_eps"]
    a = lp["attn"]
    h = rms_norm(x, lp["norm1"], eps)
    q = rope(matmul(h, a["wq"], precision).reshape(b, s, h_q, dh),
             m["rope_theta"])
    k = rope(matmul(h, a["wk"], precision).reshape(b, s, h_kv, dh),
             m["rope_theta"])
    v = matmul(h, a["wv"], precision).reshape(b, s, h_kv, dh)
    k = jnp.repeat(k, h_q // h_kv, axis=2)
    v = jnp.repeat(v, h_q // h_kv, axis=2)
    scores = einsum("bqhd,bkhd->bhqk", q, k, precision) / math.sqrt(dh)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    att = einsum("bhqk,bkhd->bqhd", probs, v, precision).reshape(b, s, -1)
    x = x + matmul(att, a["wo"], precision)
    h = rms_norm(x, lp["norm2"], eps)
    gate, up = jnp.split(matmul(h, lp["mlp"]["wi"], precision), 2, axis=-1)
    return x + matmul(silu(gate) * up, lp["mlp"]["wo"], precision)


@partial(jax.jit, static_argnums=(2, 3))
def _layer_jit(lp, x, m_items, precision):
    return layer(lp, x, dict(m_items), precision)


@partial(jax.jit, static_argnums=(3, 4, 5))
def _head_jit(top, x, start, m_items, stop, precision):
    m = dict(m_items)
    h = rms_norm(jax.lax.dynamic_slice_in_dim(x, start, stop, axis=1),
                 top["final_norm"], m["norm_eps"])
    return masked_logits(h, top["lm_head"], m["vocab"], precision)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def logits(m: Mapping, root, tokens, start: int, served_dtype,
           precisions=("f32",)):
    """Logits at positions ``start..`` of each row of ``tokens`` (R, S):
    ``{precision: (R, S - start, V_padded) float32}``.  The weights are the
    seed's, rounded to ``served_dtype`` as the program holds them."""
    items = weights.frozen(m)
    top = _f32(weights.top_params(m, root, served_dtype))
    x0 = jnp.take(top["embed"], jnp.asarray(tokens), axis=0)
    xs = {p: x0 for p in precisions}
    for i in range(m["n_layers"]):
        lp = _f32(weights.layer_params(m, root, i, served_dtype))
        xs = {p: _layer_jit(lp, x, items, p) for p, x in xs.items()}
        del lp
    n = tokens.shape[1] - start
    return {p: _head_jit(top, x, start, items, n, p) for p, x in xs.items()}
