"""Shared model layers: norms, rotary embeddings, attention (naive / chunked
online-softmax / decode), and gated MLPs.  Pure functions over param dicts;
activation sharding via ``repro.distributed.constrain``.
"""

from __future__ import annotations

import functools
import math
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed import constrain, current_mesh
from repro.kernels.decode_attention import max_block_k
from repro.kernels.ops import flash_decode_stacked

DATA = ("pod", "data")  # batch axes (sanitized away when mesh lacks "pod")
MODEL = "model"
# ``ModelConfig.attn_scale`` -> the divisor of d_head under the square root
SCALE_DIVISOR = {"head": 1, "half_head": 2}


# ---------------------------------------------------------------------------
# Norms / positions
# ---------------------------------------------------------------------------


def rms_norm(x: jnp.ndarray, scale: jnp.ndarray, eps: float = 1e-5) -> jnp.ndarray:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return ((x * jax.lax.rsqrt(var + eps)) * scale.astype(jnp.float32)).astype(dtype)


def rope_frequencies(d_head: int, theta: float) -> jnp.ndarray:
    return 1.0 / (theta ** (jnp.arange(0, d_head, 2, dtype=jnp.float32) / d_head))


def apply_rope(x: jnp.ndarray, positions: jnp.ndarray, theta: float) -> jnp.ndarray:
    """x: (..., S, H, D); positions: (..., S)."""
    d = x.shape[-1]
    freqs = rope_frequencies(d, theta)  # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, D/2)
    cos = jnp.cos(angles)[..., None, :]  # (..., S, 1, D/2)
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def sinusoidal_positions(length: int, dim: int) -> jnp.ndarray:
    pos = jnp.arange(length, dtype=jnp.float32)[:, None]
    div = jnp.exp(jnp.arange(0, dim, 2, dtype=jnp.float32) * (-math.log(1e4) / dim))
    table = jnp.zeros((length, dim), jnp.float32)
    table = table.at[:, 0::2].set(jnp.sin(pos * div))
    table = table.at[:, 1::2].set(jnp.cos(pos * div))
    return table


# ---------------------------------------------------------------------------
# Attention
# ---------------------------------------------------------------------------


def _group_query(q: jnp.ndarray, n_kv: int) -> jnp.ndarray:
    """(B,S,H,D) -> (B,S,K,G,D)."""
    b, s, h, d = q.shape
    return q.reshape(b, s, n_kv, h // n_kv, d)


def naive_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
    causal: bool = True, window: int = 0,
    q_offset: int = 0, softcap: float = 0.0, scale: Optional[float] = None,
) -> jnp.ndarray:
    """Materializes the full (S, T) score matrix — the paper-baseline path.

    q: (B,S,H,D); k/v: (B,T,K,D).  Returns (B,S,H,D).  Scores are scaled by
    ``scale``, by default D**-0.5.
    """
    b, s, h, d = q.shape
    t, n_kv = k.shape[1], k.shape[2]
    qg = _group_query(q, n_kv)
    scale = 1.0 / math.sqrt(d) if scale is None else scale
    # Native-dtype operands with f32 accumulation: never materialize an f32
    # copy of K/V (2x HBM) — MXU accumulates in f32 anyway.
    scores = jnp.einsum("bskgd,btkd->bkgst", qg, k,
                        preferred_element_type=jnp.float32) * scale
    if softcap > 0:
        scores = softcap * jnp.tanh(scores / softcap)
    qpos = jnp.arange(s) + q_offset
    kpos = jnp.arange(t)
    mask = jnp.ones((s, t), bool)
    if causal:
        mask &= kpos[None, :] <= qpos[:, None]
    if window > 0:
        mask &= kpos[None, :] > qpos[:, None] - window
    scores = jnp.where(mask[None, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", probs.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, s, h, d).astype(q.dtype)


def chunked_attention(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
    chunk: int = 512, causal: bool = True, window: int = 0,
    q_offset: int = 0, softcap: float = 0.0, scale: Optional[float] = None,
) -> jnp.ndarray:
    """Online-softmax attention over KV chunks (flash-style in XLA).

    Peak memory O(S * chunk) instead of O(S * T); the Pallas kernel
    (`repro.kernels.flash_attention`) is the TPU-tiled version of this
    algorithm and is validated against the same oracle.
    """
    b, s, h, d = q.shape
    t, n_kv = k.shape[1], k.shape[2]
    if t % chunk != 0:
        return naive_attention(q, k, v, causal, window, q_offset, softcap,
                               scale)
    n_chunks = t // chunk
    qg = _group_query(q, n_kv)
    scale = 1.0 / math.sqrt(d) if scale is None else scale
    qpos = (jnp.arange(s) + q_offset)[:, None]  # (S,1)

    kc = k.reshape(b, n_chunks, chunk, n_kv, d)
    vc = v.reshape(b, n_chunks, chunk, n_kv, d)

    def body(carry, inputs):
        m, l, acc = carry
        j, kj, vj = inputs
        scores = jnp.einsum("bskgd,btkd->bkgst", qg, kj,
                            preferred_element_type=jnp.float32) * scale
        if softcap > 0:
            scores = softcap * jnp.tanh(scores / softcap)
        kpos = j * chunk + jnp.arange(chunk)[None, :]  # (1,chunk)
        # Additive (S, chunk) f32 bias instead of a pred mask + where: the
        # boolean mask gets hoisted/broadcast to full scores shape across
        # all chunk iterations by XLA (hundreds of MB of pred buffers);
        # the bias stays (S, chunk) and fuses into the add (§Perf iter 7).
        bias = jnp.zeros((s, chunk), jnp.float32)
        if causal:
            bias = jnp.where(kpos <= qpos, bias, -1e30)
        if window > 0:
            bias = jnp.where(kpos > qpos - window, bias, -1e30)
        scores = scores + bias[None, None, None]
        m_j = jnp.max(scores, axis=-1)
        m_new = jnp.maximum(m, m_j)
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(scores - m_new[..., None])
        l_new = l * alpha + jnp.sum(p, axis=-1)
        pv = jnp.einsum("bkgst,btkd->bkgsd", p.astype(vj.dtype), vj,
                        preferred_element_type=jnp.float32)
        acc_new = acc * alpha[..., None] + pv
        return (m_new, l_new, acc_new), None

    n_g = h // n_kv
    m0 = jnp.full((b, n_kv, n_g, s), -1e30, jnp.float32)
    l0 = jnp.zeros((b, n_kv, n_g, s), jnp.float32)
    acc0 = jnp.zeros((b, n_kv, n_g, s, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, acc0),
        (jnp.arange(n_chunks), jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0)),
    )
    out = acc / jnp.maximum(l[..., None], 1e-30)
    out = jnp.moveaxis(out, 3, 1)  # (b,s,k,g,d)
    return out.reshape(b, s, h, d).astype(q.dtype)


def decode_attention(
    q: jnp.ndarray, k_cache: jnp.ndarray, v_cache: jnp.ndarray,
    lengths: jnp.ndarray, window: int = 0, softcap: float = 0.0,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Single-position attention against a (B,T,K,D) cache.

    q: (B,1,H,D); lengths: (B,) number of valid cache positions (inclusive of
    the current token).  Memory O(T) — the XLA counterpart of flash-decode.
    """
    b, _, h, d = q.shape
    t, n_kv = k_cache.shape[1], k_cache.shape[2]
    qg = _group_query(q, n_kv)[:, 0].astype(k_cache.dtype)  # (B,K,G,D)
    scale = 1.0 / math.sqrt(d) if scale is None else scale
    # Cache stays in its storage dtype; f32 accumulation via the MXU.  An
    # .astype(f32) here would materialize a second full-cache-sized buffer.
    scores = jnp.einsum("bkgd,btkd->bkgt", qg, k_cache,
                        preferred_element_type=jnp.float32) * scale
    if softcap > 0:
        scores = softcap * jnp.tanh(scores / softcap)
    kpos = jnp.arange(t)[None, :]  # (1,T)
    valid = kpos < lengths[:, None]
    if window > 0:
        valid &= kpos >= jnp.maximum(lengths[:, None] - window, 0)
    scores = jnp.where(valid[:, None, None], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgt,btkd->bkgd", probs.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, 1, h, d).astype(q.dtype)


def stacked_decode_attention(
    q: jnp.ndarray, k_stack: jnp.ndarray, v_stack: jnp.ndarray,
    layer: jnp.ndarray, lengths: jnp.ndarray, window: int = 0,
    softcap: float = 0.0, scale: Optional[float] = None,
) -> jnp.ndarray:
    """``decode_attention`` against layer ``layer`` of a stacked (L,B,T,K,D)
    cache.

    On the TPU the flash-decode kernel reads the layer's valid blocks where
    the stack holds them.  Off the TPU, and where the kernel lacks a feature
    (a window over a linear cache, a logit softcap), where not even its
    smallest block of every KV head fits its VMEM budget, or where the cache
    is sharded over a mesh, the XLA read takes the layer's slice.
    """
    def xla(q, k_stack, v_stack, layer, lengths):
        return decode_attention(
            q, jax.lax.dynamic_index_in_dim(k_stack, layer, keepdims=False),
            jax.lax.dynamic_index_in_dim(v_stack, layer, keepdims=False),
            lengths, window=window, softcap=softcap, scale=scale)

    mesh = current_mesh()
    _, _, h, d = q.shape
    fits = max_block_k(k_stack.shape[3], d, h, k_stack.dtype.itemsize) > 0
    if (window > 0 or softcap > 0 or not fits
            or (mesh is not None and mesh.mesh.size > 1)):
        return xla(q, k_stack, v_stack, layer, lengths)
    return jax.lax.platform_dependent(
        q, k_stack, v_stack, layer, lengths,
        tpu=functools.partial(flash_decode_stacked, scale=scale),
        default=xla)


# ---------------------------------------------------------------------------
# Attention block (projection + rope + qk-norm wrapper)
# ---------------------------------------------------------------------------


def attention_block(
    params: Dict[str, jnp.ndarray],
    x: jnp.ndarray,
    cfg,
    run,
    positions: jnp.ndarray,
    kv_cache: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
    cache_pos: Optional[jnp.ndarray] = None,
    cache_layer: Optional[jnp.ndarray] = None,
    causal: bool = True,
    kv_x: Optional[jnp.ndarray] = None,
    use_rope: bool = True,
) -> Tuple[jnp.ndarray, Optional[Tuple[jnp.ndarray, jnp.ndarray]]]:
    """Returns (output, new_kv).

    * prefill/train: ``new_kv`` is this segment's rope'd (K, V) — the caller
      may install it as the cache.
    * decode (``kv_cache`` + scalar ``cache_pos`` and ``cache_layer`` given):
      ``kv_cache`` is the stacked (L,B,T,K,Dc) cache of every layer (Dc:
      ``cfg.cache_head_dim``); the new
      token's K/V is written in place at (``cache_layer``, ``cache_pos``),
      attention reads that layer from the stack, and ``new_kv`` is the
      updated stack.
    * ``kv_x`` selects cross-attention (encoder output as KV source, no rope).
    """
    b, s, _ = x.shape
    h, k_heads, d = cfg.n_heads, cfg.n_kv_heads, cfg.d_head
    kv_src = x if kv_x is None else kv_x
    scale = 1.0 / math.sqrt(d / SCALE_DIVISOR[cfg.attn_scale])

    q = (x @ params["wq"]).reshape(b, s, h, d)
    kk = (kv_src @ params["wk"]).reshape(b, kv_src.shape[1], k_heads, d)
    vv = (kv_src @ params["wv"]).reshape(b, kv_src.shape[1], k_heads, d)
    q = constrain(q, DATA, None, MODEL, None)
    kk = constrain(kk, DATA, None, MODEL, None)
    vv = constrain(vv, DATA, None, MODEL, None)

    if cfg.qk_norm:
        q = rms_norm(q, params["q_norm"], cfg.norm_eps)
        kk = rms_norm(kk, params["k_norm"], cfg.norm_eps)
    if use_rope and kv_x is None:
        q = apply_rope(q, positions, cfg.rope_theta)
        kk = apply_rope(kk, positions, cfg.rope_theta)

    if kv_cache is not None:
        k_cache, v_cache = kv_cache
        # A cache of heads wider than d (``cfg.kv_head_align``): zeros past
        # d in q, K and V leave the scores, and the output's first d lanes.
        wide = k_cache.shape[-1] - d
        if wide:
            q, kk, vv = (jnp.pad(a, ((0, 0),) * 3 + ((0, wide),))
                         for a in (q, kk, vv))
        at = (cache_layer, 0, cache_pos, 0, 0)
        k_cache = jax.lax.dynamic_update_slice(
            k_cache, kk[None].astype(k_cache.dtype), at)
        v_cache = jax.lax.dynamic_update_slice(
            v_cache, vv[None].astype(v_cache.dtype), at)
        lengths = jnp.full((b,), cache_pos + s, dtype=jnp.int32)
        out = stacked_decode_attention(q, k_cache, v_cache, cache_layer,
                                       lengths, window=cfg.window,
                                       softcap=cfg.attn_logit_softcap,
                                       scale=scale)
        if wide:
            out = out[..., :d]
        new_kv = (k_cache, v_cache)
    else:
        if run.attention_impl == "naive":
            out = naive_attention(q, kk, vv, causal=causal, window=cfg.window,
                                  softcap=cfg.attn_logit_softcap, scale=scale)
        else:
            out = chunked_attention(q, kk, vv, chunk=run.attention_chunk,
                                    causal=causal, window=cfg.window,
                                    softcap=cfg.attn_logit_softcap,
                                    scale=scale)
        new_kv = (kk, vv)
    out = constrain(out, DATA, None, MODEL, None)
    y = out.reshape(b, s, h * d) @ params["wo"]
    return constrain(y, DATA, None, None), new_kv


# ---------------------------------------------------------------------------
# MLP
# ---------------------------------------------------------------------------


def mlp_block(params: Dict[str, jnp.ndarray], x: jnp.ndarray, act: str,
              adapter: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None
              ) -> jnp.ndarray:
    """``swiglu`` and ``geglu`` (exact-erf GELU) gate one half of ``wi``'s
    output with the other; ``adapter`` (down, up), a low-rank map added to
    that gate_up projection."""
    if act in ("swiglu", "geglu"):
        gate_up = x @ params["wi"]  # (..., 2*ff)
        if adapter is not None:
            gate_up = gate_up + (x @ adapter[0]) @ adapter[1]
        gate_up = constrain(gate_up, DATA, None, MODEL)
        gate, up = jnp.split(gate_up, 2, axis=-1)
        if act == "swiglu":
            hidden = jax.nn.silu(gate) * up
        else:
            hidden = jax.nn.gelu(gate, approximate=False) * up
    else:
        hidden = jax.nn.gelu(x @ params["wi"])
        hidden = constrain(hidden, DATA, None, MODEL)
    y = hidden @ params["wo"]
    return constrain(y, DATA, None, None)
