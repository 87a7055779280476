"""Jit'd public wrappers for the Pallas kernels.

These map model-layer layouts (B, S, H, D) onto the kernels' flattened
layouts and broadcast GQA KV heads.  They compile for the TPU; off the TPU a
caller asks for ``interpret=True`` (CPU validation mode — the kernel body
runs in Python, proving the tiling/masking logic against ``ref.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention import decode_attention_stacked
from repro.kernels.flash_attention import flash_attention_bhsd
from repro.kernels.rmsnorm import rmsnorm_rows
from repro.kernels.ssd_scan import ssd_intra_chunk


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, *, causal=True, window=0, block_q=128,
                    block_k=128, interpret=False):
    """q: (B,S,H,D); k/v: (B,T,K,D) GQA -> (B,S,H,D)."""
    b, s, h, d = q.shape
    t, kh = k.shape[1], k.shape[2]
    g = h // kh
    # Broadcast KV heads to query heads, flatten (B,H) -> BH.
    kq = jnp.repeat(k, g, axis=2)
    vq = jnp.repeat(v, g, axis=2)
    qf = q.transpose(0, 2, 1, 3).reshape(b * h, s, d)
    kf = kq.transpose(0, 2, 1, 3).reshape(b * h, t, d)
    vf = vq.transpose(0, 2, 1, 3).reshape(b * h, t, d)
    of = flash_attention_bhsd(qf, kf, vf, causal=causal, window=window,
                              block_q=min(block_q, s), block_k=min(block_k, t),
                              interpret=interpret)
    return of.reshape(b, h, s, d).transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("block_k", "scale",
                                             "interpret"))
def flash_decode_stacked(q, k_stack, v_stack, layer, lengths, *, block_k=None,
                         scale=None, interpret=False):
    """q: (B,1,H,D); k/v stack: (L,B,T,K,D), read at ``layer`` in place;
    lengths (B,) -> (B,1,H,D); scores scaled by ``scale`` (D**-0.5)."""
    out = decode_attention_stacked(q[:, 0], k_stack, v_stack, layer, lengths,
                                   block_k=block_k, scale=scale,
                                   interpret=interpret)
    return out[:, None]


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def flash_decode(q, k_cache, v_cache, lengths, *, block_k=512, interpret=False):
    """q: (B,1,H,D); k/v cache: (B,T,K,D); lengths (B,) -> (B,1,H,D): the
    one-layer case of ``flash_decode_stacked``."""
    return flash_decode_stacked(
        q, k_cache[None], v_cache[None], jnp.zeros((), jnp.int32), lengths,
        block_k=min(block_k, k_cache.shape[1]), interpret=interpret)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_chunk_dual(xdt, cum, bm, cm, *, interpret=False):
    """Kernel-backed intra-chunk SSD (see mamba2.ssd_chunked for the full op)."""
    return ssd_intra_chunk(xdt, cum, bm, cm, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def fused_rmsnorm(x, w, *, eps=1e-5, interpret=False):
    """x: (..., d) RMSNorm with learned scale."""
    shape = x.shape
    rows = 1
    for dim in shape[:-1]:
        rows *= dim
    x2 = x.reshape(rows, shape[-1])
    block = rows
    for cand in (256, 128, 64, 32, 16, 8, 4, 2, 1):
        if rows % cand == 0:
            block = cand
            break
    y = rmsnorm_rows(x2, w, eps=eps, block_rows=block, interpret=interpret)
    return y.reshape(shape)
