"""The hybrid family: Zamba2 (arXiv:2411.15242; ``transformers``'
``modeling_zamba2.py``, whose equations this follows).

What the harness knows of the family: its parameter leaves (``leaves``),
its counts over the stack (``stack_params`` ... ``decode_state_bytes``,
which ``flops.py`` adds to the embedding and head, and ``decode_kv_bytes``,
the attention's share of the decode bytes), its float32 reference
(``logits``) and its CPU sizes (``TINY``).

Every layer is a Mamba-2 layer; ``hybrid_pattern`` ("M" or "H" a layer, the
first ``n_layers`` kept) marks the layers a shared transformer block runs
before.  Invocation j (the j-th "H") runs block j % ``hybrid_blocks``:

    t = concat(x, x0)                     x0: the token embeddings
    t = attention(rmsnorm(t))             2d wide in, d out; no residual
    t = mlp(rmsnorm(t), adapter_j)        gelu(g) * u, g|u = t W + t A_j B_j
    t = t L_j                             the invocation's own d x d map
    x = x + mamba(rmsnorm(x + t))         t enters that layer's input only

and a layer without a block is ``x = x + mamba(rmsnorm(x))``.  Attention is
multi-head over heads of ``d_head`` (Zamba2: 2d / heads) with rotary
position embedding on every dimension (rotate-half form) and scores scaled
by (d_head / 2) ** -0.5, Zamba2's.  The GELU is the exact (erf) one.

The Mamba-2 layer: one input projection to (z, x, B, C, dt), B and C in
``ssm_groups`` groups of ``ssm_state``; a depthwise causal convolution of
width K with bias over (x, B, C), then SiLU; dt = softplus(dt + dt_bias);
A = -exp(A_log); per time step t and head h, head h reading group
h // (heads / groups),

    h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t^T      (N x P state per head)
    y_t = C_t^T h_t + D x_t

then y * silu(z) normalised by RMS over each group's d_inner / groups
channels, scaled, and the output projection.  A final RMSNorm precedes the
head, which is the embedding (tied).

Departures from ``modeling_zamba2.py``: dt is not clamped below at
``time_step_min`` (its plain-PyTorch path clamps; the fused kernels the
published model runs on do not, with ``time_step_limit`` null); weights are
drawn from the seed (below), not trained.

It runs layer by layer on the device, drawing each layer's, block's and
invocation's weights from the seed (``weights.layer_params``) as the served
dtype and computing in float32 at ``Precision.HIGHEST``, one of them at a
time.  The recurrence runs one step at a time (``lax.scan`` over time),
independent of the chunked dual form the program computes; attention is
computed whole (causal mask, then softmax), without any cache.

Weights follow Zamba2's initialisation (``_init_weights``): matrices
N(0, 0.02^2); A = 1, 2, ..., heads (``A_log`` its log); dt from
log-uniform [1e-3, 1e-1] (floored at 1e-4) stored as its inverse
softplus; D = 1; RMSNorm scales 1 + N(0, 0.1^2), so that a scale applied
wrongly shows.  The convolution's weight and bias are U(-1/sqrt(K),
1/sqrt(K)), PyTorch's default and the Mamba-2 release's (the generic
N(0, 0.02^2) and zero of ``transformers`` would leave it next to nothing).  Two
departures, so that the comparison can see what it must: the query and key
projections are drawn so that attention scores spread by ~``QK_SPREAD``, as
a trained model's heads do (at 0.02 they spread by ~0.02^2 * 2d * sqrt(2),
and attention over a long prompt is nearly uniform: a decode that read a
stale cache would then give nearly the same logits); the tied embedding is
drawn N(0, 0.02^2 * 3584 / d), 0.02 at Zamba2-7B's width and the same
spread of logits (~1.2) at any other.

Operations count the SSD layer as its chunked dual form with chunk Q (per
chunk and group the C B^T scores once; per head the masked (Q, Q) product
with X, the chunk state and the inter-chunk output) and the depthwise
convolution; each invocation's attention as scores and weighted sum over
its keys; matrix products as the parameters a token reads in them, a
shared block's once per invocation.
"""

from __future__ import annotations

import math
from functools import partial
from typing import Mapping

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import flops, weights
from benchmarks.chip.reference.numerics import (
    HIGHEST, einsum, masked_logits, matmul, rms_norm, silu)
from benchmarks.chip.weights import Leaf

# The query and key projections are drawn N(0, QK_SPREAD / (sqrt(2) * 2d)):
# scores q.k * (d_head / 2)**-0.5 over a 2d-wide normalised input then
# spread by ~QK_SPREAD.
QK_SPREAD = 4.0
EMBED_WIDTH = 3584  # the width at which the tied embedding is N(0, 0.02^2)

# Widths and lengths small enough for the CPU rehearsal: both shared blocks
# twice, two invocations back to back, two groups of B and C.
TINY = {"n_layers": 7, "hybrid_pattern": "MHHMHHM", "d_model": 256,
        "n_heads": 4, "n_kv_heads": 4, "d_head": 128, "d_ff": 512,
        "vocab": 1000, "ssm_state": 16, "ssm_head_dim": 32, "ssm_chunk": 32,
        "adapter_rank": 8}


def _sizes(m: Mapping):
    """Inner width, state size N, groups, heads, head width P."""
    p = m.get("ssm_head_dim", 64)
    d_inner = m.get("ssm_expand", 2) * m["d_model"]
    return d_inner, m["ssm_state"], m.get("ssm_groups", 1), d_inner // p, p


def hybrid_ids(m: Mapping) -> list:
    """The layers a shared block runs before (invocation j: the j-th)."""
    pattern = m["hybrid_pattern"][:m["n_layers"]]
    assert len(pattern) == m["n_layers"], pattern
    return [i for i, kind in enumerate(pattern) if kind == "H"]


def _a_log(key, shape):
    return jnp.broadcast_to(
        jnp.log(jnp.arange(1, shape[-1] + 1, dtype=jnp.float32)), shape)


def _dt_bias(key, shape):
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                    np.log(1e-3), np.log(1e-1)))
    dt = jnp.maximum(dt, 1e-4)  # time_step_floor
    return dt + jnp.log(-jnp.expm1(-dt))  # inverse softplus


def leaves(m: Mapping) -> dict:
    """Each Mamba-2 layer's leaves (``n_layers`` deep), each shared block's
    (``hybrid_blocks``), each invocation's (one a shared block's layer);
    the tied embedding's law."""
    d, ff, r = m["d_model"], m["d_ff"], m.get("adapter_rank", 0)
    di, n, g, nh, _ = _sizes(m)
    k = m.get("ssm_conv", 4)
    conv_ch = di + 2 * g * n
    hq, hkv = m["n_heads"] * m["d_head"], m["n_kv_heads"] * m["d_head"]
    nb, n_inv = m.get("hybrid_blocks", 1), len(hybrid_ids(m))
    qk = ("normal", (QK_SPREAD / (math.sqrt(2) * 2 * d)) ** 0.5)
    out = {
        ("embed",): Leaf((flops.padded_vocab(m), d),
                         ("normal", 0.02 * (EMBED_WIDTH / d) ** 0.5), 0),
        ("layers", "mamba", "in_proj"):
            Leaf((d, 2 * di + 2 * g * n + nh), ("normal", 0.02)),
        ("layers", "mamba", "conv_w"): Leaf((k, conv_ch),
                                            ("uniform", k ** -0.5)),
        ("layers", "mamba", "A_log"): Leaf((nh,), _a_log),
        ("layers", "mamba", "D"): Leaf((nh,), ("ones",)),
        ("layers", "mamba", "dt_bias"): Leaf((nh,), _dt_bias),
        ("layers", "mamba", "ssm_norm"): Leaf((di,), ("scale",)),
        ("layers", "mamba", "out_proj"): Leaf((di, d), ("normal", 0.02)),
        ("layers", "norm1"): Leaf((d,), ("scale",)),
        ("shared", "attn", "wq"): Leaf((2 * d, hq), qk, nb),
        ("shared", "attn", "wk"): Leaf((2 * d, hkv), qk, nb),
        ("shared", "attn", "wv"): Leaf((2 * d, hkv), ("normal", 0.02), nb),
        ("shared", "attn", "wo"): Leaf((hq, d), ("normal", 0.02), nb),
        ("shared", "mlp", "wi"): Leaf((d, 2 * ff), ("normal", 0.02), nb),
        ("shared", "mlp", "wo"): Leaf((ff, d), ("normal", 0.02), nb),
        ("shared", "norm1"): Leaf((2 * d,), ("scale",), nb),
        ("shared", "norm2"): Leaf((d,), ("scale",), nb),
        ("invocations", "linear"): Leaf((d, d), ("normal", 0.02), n_inv),
    }
    if m.get("ssm_conv_bias", False):
        out[("layers", "mamba", "conv_b")] = Leaf((conv_ch,),
                                                  ("uniform", k ** -0.5))
    if r:
        out[("invocations", "adapter_down")] = Leaf((d, r), ("normal", 0.02),
                                                    n_inv)
        out[("invocations", "adapter_up")] = Leaf((r, 2 * ff),
                                                  ("normal", 0.02), n_inv)
    return out


def _counts(m: Mapping):
    """Parameters: (a Mamba-2 layer's, in its matrix products; a shared
    block's, in its products; an invocation's, all in products)."""
    d, ff, r = m["d_model"], m["d_ff"], m.get("adapter_rank", 0)
    di, n, g, nh, _ = _sizes(m)
    conv_ch = di + 2 * g * n
    taps = m.get("ssm_conv", 4) + int(m.get("ssm_conv_bias", False))
    mamba_mm = d * (2 * di + 2 * g * n + nh) + di * d  # in_proj, out_proj
    # conv, A, D, dt_bias, gated-norm scale, pre-norm scale
    mamba = mamba_mm + taps * conv_ch + 3 * nh + di + d
    hq, hkv = m["n_heads"] * m["d_head"], m["n_kv_heads"] * m["d_head"]
    block_mm = 2 * d * (hq + 2 * hkv) + hq * d + 3 * d * ff
    return (mamba, mamba_mm), (block_mm + 3 * d, block_mm), \
        r * (d + 2 * ff) + d * d


def stack_params(m: Mapping) -> int:
    """Parameters of every Mamba-2 layer, shared block and invocation."""
    (mamba, _), (block, _), inv = _counts(m)
    return m["n_layers"] * mamba + m.get("hybrid_blocks", 1) * block \
        + len(hybrid_ids(m)) * inv


def matmul_params(m: Mapping) -> int:
    """Parameters a token reads in matrix products: every Mamba-2 layer's
    projections, and at each invocation its block's and its own."""
    (_, mamba_mm), (_, block_mm), inv = _counts(m)
    return m["n_layers"] * mamba_mm + len(hybrid_ids(m)) * (block_mm + inv)


def mixer_flops(m: Mapping, keys: float) -> float:
    """Per token: each Mamba-2 layer's SSD (chunked form) and depthwise
    convolution, and each invocation's attention over ``keys`` keys."""
    di, n, g, nh, p = _sizes(m)
    q = m.get("ssm_chunk", 256)
    scores = g * 2.0 * q * n  # C_i . B_j over the chunk, per group
    per_head = 2.0 * q * p + 2.0 * n * p + 2.0 * n * p  # M X, state, C h
    conv = 2.0 * m.get("ssm_conv", 4) * (di + 2 * g * n)
    attn = 4.0 * m["n_heads"] * m["d_head"] * keys
    return m["n_layers"] * (scores + nh * per_head + conv) \
        + len(hybrid_ids(m)) * attn


def decode_mixer_flops(m: Mapping, cache_len: int) -> float:
    """A decode row: each Mamba-2 layer's recurrent step (per head the
    state's update and its read, a multiply-add an element each) and
    convolution; each invocation's attention over ``cache_len`` positions
    (its own included)."""
    di, n, g, nh, p = _sizes(m)
    step = nh * 4.0 * n * p + 2.0 * m.get("ssm_conv", 4) * (di + 2 * g * n)
    attn = 4.0 * m["n_heads"] * m["d_head"] * cache_len
    return m["n_layers"] * step + len(hybrid_ids(m)) * attn


def decode_kv_bytes(m: Mapping, cache_len: int, cache_bytes: int) -> int:
    """The attention K and V a decode row reads at ``cache_len`` (its new
    position included), every invocation."""
    per_pos = 2 * m["n_kv_heads"] * m["d_head"] * cache_bytes
    return len(hybrid_ids(m)) * per_pos * cache_len


def decode_state_bytes(m: Mapping, cache_len: int, cache_bytes: int) -> int:
    """A decode row's state traffic: every invocation's valid K and V
    prefix read and new position written; each Mamba-2 layer's SSM state
    (heads x N x P) and convolution state (K - 1 positions) read and
    written."""
    di, n, g, nh, p = _sizes(m)
    conv = (m.get("ssm_conv", 4) - 1) * (di + 2 * g * n)
    per_layer = 2 * (nh * n * p + conv) * cache_bytes
    return decode_kv_bytes(m, cache_len, cache_bytes) \
        + m["n_layers"] * per_layer


def rope(x, theta):
    """x: (B, S, H, D) at positions 0..S-1, the rotate-half form."""
    s, d = x.shape[1], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angle)[None, :, None], jnp.sin(angle)[None, :, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _recurrence(xs, dt, a, bm, cm):
    """xs (B,S,H,P), dt (B,S,H), a (H,), bm/cm (B,S,H,N) -> y (B,S,H,P)."""
    b, _, nh, p = xs.shape
    n = bm.shape[-1]

    def step(state, inp):
        x_t, dt_t, b_t, c_t = inp
        state = jnp.exp(dt_t * a)[:, :, None, None] * state \
            + b_t[..., None] * (x_t * dt_t[..., None])[:, :, None, :]
        return state, jnp.einsum("bhn,bhnp->bhp", c_t, state,
                                 precision=HIGHEST)

    inputs = tuple(jnp.moveaxis(v, 1, 0) for v in (xs, dt, bm, cm))
    _, ys = jax.lax.scan(step, jnp.zeros((b, nh, n, p), jnp.float32), inputs)
    return jnp.moveaxis(ys, 0, 1)


def mamba_layer(lp, x, inject, m: Mapping, precision: str = "f32"):
    """One Mamba-2 layer; ``inject`` is added to its input only."""
    b, s, _ = x.shape
    di, n, g, nh, p = _sizes(m)
    mp = lp["mamba"]
    h = rms_norm(x + inject, lp["norm1"], m["norm_eps"])
    proj = matmul(h, mp["in_proj"], precision)
    z, xbc, dt = (proj[..., :di], proj[..., di:2 * di + 2 * g * n],
                  proj[..., 2 * di + 2 * g * n:])
    k = mp["conv_w"].shape[0]
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    xbc = sum(mp["conv_w"][i] * padded[:, i:i + s] for i in range(k))
    xbc = silu(xbc + mp["conv_b"] if "conv_b" in mp else xbc)
    xs = xbc[..., :di].reshape(b, s, nh, p)

    def per_head(v):  # (B, S, G*N) -> (B, S, H, N), head h: group h // (H/G)
        return jnp.repeat(v.reshape(b, s, g, n), nh // g, axis=2)

    bm, cm = per_head(xbc[..., di:di + g * n]), per_head(xbc[..., di + g * n:])
    dt = jax.nn.softplus(dt + mp["dt_bias"])
    y = _recurrence(xs, dt, -jnp.exp(mp["A_log"]), bm, cm)
    y = (y + mp["D"][:, None] * xs).reshape(b, s, g, di // g)
    gate = silu(z).reshape(b, s, g, di // g)
    y = rms_norm(y * gate, mp["ssm_norm"].reshape(g, di // g),
                 m["norm_eps"]).reshape(b, s, di)
    return x + matmul(y, mp["out_proj"], precision)


def shared_block(bp, ip, x, x0, m: Mapping, precision: str = "f32"):
    """A shared block at one invocation (``ip``: its adapter and linear
    map): what it adds to the next Mamba-2 layer's input."""
    b, s, d = x.shape
    h_q, h_kv, dh = m["n_heads"], m["n_kv_heads"], m["d_head"]
    eps, a = m["norm_eps"], bp["attn"]
    h = rms_norm(jnp.concatenate([x, x0], axis=-1), bp["norm1"], eps)
    q = rope(matmul(h, a["wq"], precision).reshape(b, s, h_q, dh),
             m["rope_theta"])
    k = rope(matmul(h, a["wk"], precision).reshape(b, s, h_kv, dh),
             m["rope_theta"])
    v = matmul(h, a["wv"], precision).reshape(b, s, h_kv, dh)
    k = jnp.repeat(k, h_q // h_kv, axis=2)
    v = jnp.repeat(v, h_q // h_kv, axis=2)
    scores = einsum("bqhd,bkhd->bhqk", q, k, precision) / math.sqrt(dh / 2)
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    att = einsum("bhqk,bkhd->bqhd", probs, v, precision).reshape(b, s, -1)
    h = rms_norm(matmul(att, a["wo"], precision), bp["norm2"], eps)
    gate_up = matmul(h, bp["mlp"]["wi"], precision)
    if "adapter_down" in ip:
        gate_up = gate_up + matmul(matmul(h, ip["adapter_down"], precision),
                                   ip["adapter_up"], precision)
    gate, up = jnp.split(gate_up, 2, axis=-1)
    h = jax.nn.gelu(gate, approximate=False) * up
    return matmul(matmul(h, bp["mlp"]["wo"], precision), ip["linear"],
                  precision)


@partial(jax.jit, static_argnums=(3, 4))
def _mamba_jit(lp, x, inject, m_items, precision):
    return mamba_layer(lp, x, inject, dict(m_items), precision)


@partial(jax.jit, static_argnums=(4, 5))
def _block_jit(bp, ip, x, x0, m_items, precision):
    return shared_block(bp, ip, x, x0, dict(m_items), precision)


@partial(jax.jit, static_argnums=(3, 4, 5))
def _head_jit(top, x, start, m_items, stop, precision):
    m = dict(m_items)
    h = rms_norm(jax.lax.dynamic_slice_in_dim(x, start, stop, axis=1),
                 top["final_norm"], m["norm_eps"])
    return masked_logits(h, top["embed"].T, m["vocab"], precision)


def _f32(tree):
    return jax.tree.map(lambda a: a.astype(jnp.float32), tree)


def logits(m: Mapping, root, tokens, start: int, served_dtype,
           precisions=("f32",)):
    """Logits at positions ``start..`` of each row of ``tokens`` (R, S):
    ``{precision: (R, S - start, V_padded) float32}``.  The weights are the
    seed's, rounded to ``served_dtype`` as the program holds them."""
    items = weights.frozen(m)
    invocations = {layer: j for j, layer in enumerate(hybrid_ids(m))}
    nb = m.get("hybrid_blocks", 1)
    with jax.default_matmul_precision("highest"):
        top = _f32(weights.top_params(m, root, served_dtype))
        x0 = jnp.take(top["embed"], jnp.asarray(tokens), axis=0)
        xs = {p: x0 for p in precisions}
        for i in range(m["n_layers"]):
            inject = {p: jnp.zeros_like(x0) for p in precisions}
            if i in invocations:
                j = invocations[i]
                bp = _f32(weights.layer_params(m, root, j % nb, served_dtype,
                                               "shared"))
                ip = _f32(weights.layer_params(m, root, j, served_dtype,
                                               "invocations"))
                inject = {p: _block_jit(bp, ip, x, x0, items, p)
                          for p, x in xs.items()}
                del bp, ip
            lp = _f32(weights.layer_params(m, root, i, served_dtype))
            xs = {p: _mamba_jit(lp, x, inject[p], items, p)
                  for p, x in xs.items()}
            del lp, inject
        n = tokens.shape[1] - start
        return {p: _head_jit(top, x, start, items, n, p)
                for p, x in xs.items()}
