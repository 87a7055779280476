"""Flash-decode (split-KV single-query attention) Pallas TPU kernel that reads
one layer of a stacked KV cache where it is stored.

One query position per sequence against layer ``layer`` of a (L, B, T, K, D)
cache: grid = (batch, kv_blocks), KV innermost.  Each step takes a
(block_k, K, D) block, every KV head at once, straight from the stack; the
online-softmax state of all H = K * G query heads lives in VMEM scratch.  The
layer index and the per-row valid lengths come in by scalar prefetch, and
the index map clamps the block index to the row's last valid block, so
blocks past the fill are never fetched (the pipeline skips a block whose
index does not change).  The grid is ceil(T / block_k) blocks; positions of
a last, partial block past T are masked like those past the fill.

Scores are one (H, block_k * K) product per block, query heads against every
(position, KV head) row of the block, with the pairs of different heads
masked out: the block needs no relayout to split its heads.  The mask
compares two small operands, the KV head of each query head (H, 1) and of
each row of a block (1, block_k * K).

Layout: q (B, H, D), head h = kv_head * G + g; k/v (L, B, T, K, D);
layer (1,) int32; lengths (B,) int32, each at least 1.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
NAME = "decode_attention_stacked"  # the kernel's name in traces and HLO

# VMEM the kernel's blocks and score tiles may take; v5e's default scoped
# limit is 16 MiB.
VMEM_BUDGET = 12 * 2**20
MIN_BLOCK = 128  # the smallest block; where it does not fit, the XLA read


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _bytes_per_position(n_kv: int, d: int, h: int, itemsize: int) -> int:
    """VMEM per cached position of a block: K and V, double-buffered, with
    the KV-head axis padded to the sublane tile and D to the lane tile; the
    f32 score, probability and mask tiles of every query head; and the
    double-buffered row-head operand."""
    sublanes = 8 * (4 // itemsize)
    kv = 2 * 2 * _round_up(n_kv, sublanes) * _round_up(d, 128) * itemsize
    scores = 3 * _round_up(h, 8) * n_kv * 4
    row_heads = 2 * 8 * n_kv * 4
    return kv + scores + row_heads


def max_block_k(n_kv: int, d: int, h: int, itemsize: int) -> int:
    """The most positions a block may hold within ``VMEM_BUDGET``, a multiple
    of ``MIN_BLOCK``; 0 where not even ``MIN_BLOCK`` fit (use the XLA read)."""
    per = _bytes_per_position(n_kv, d, h, itemsize)
    return VMEM_BUDGET // per // MIN_BLOCK * MIN_BLOCK


def default_block_k(t: int, n_kv: int, d: int, h: int, itemsize: int) -> int:
    """Block size for a cache of ``t`` positions: as few blocks as the VMEM
    budget allows, of equal size rounded up to a multiple of 16 (the whole
    cache where it fits in one).

    Larger blocks mean fewer grid steps and smaller ones fetch less past a
    short fill; on one v5e, a yi-9b-width stack of 12 x 64 x 1280 read at a
    fill of 1100 took 3.85 ms a call in blocks of 320, 3.18 ms in 640 and
    2.99 ms in 1280, and at a fill of 300 2.11, 3.04 and 3.00 ms.
    """
    cap = max_block_k(n_kv, d, h, itemsize)
    assert cap, "the kernel does not fit VMEM at these widths"
    if t <= cap:
        return t
    n_blocks = -(-t // cap)
    return _round_up(-(-t // n_blocks), 16)


def _decode_kernel(layer_ref, len_ref, qh_ref, rh_ref, q_ref, k_ref, v_ref,
                   o_ref, m_scr, l_scr, acc_scr, *, scale: float,
                   block_k: int, n_kv_heads: int, n_kv_blocks: int,
                   ragged: bool):
    del layer_ref  # used by the index maps only
    b = pl.program_id(0)
    kj = pl.program_id(1)

    @pl.when(kj == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    length = len_ref[b]

    @pl.when(kj * block_k < length)
    def _body():
        d = q_ref.shape[1]
        rows = block_k * n_kv_heads  # row r: position r // K, KV head r % K
        valid = (length - kj * block_k) * n_kv_heads  # rows in the fill
        k = k_ref[...].reshape(rows, d)
        v = v_ref[...].reshape(rows, d)
        if ragged:  # rows of a partial last block past T hold no data
            row = jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
            v = jnp.where(row < valid, v, jnp.zeros_like(v))
        q = q_ref[...].astype(k.dtype)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        col = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        keep = (qh_ref[...] == rh_ref[...]) & (col < valid)
        s = jnp.where(keep, s, NEG_INF)

        m_prev, l_prev = m_scr[...], l_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1)[:, None])
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        l_scr[...] = l_prev * alpha + jnp.sum(p, axis=1)[:, None]
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(kj == n_kv_blocks - 1)
    def _finalize():
        o_ref[...] = (acc_scr[...] / jnp.maximum(l_scr[...], 1e-30)
                      ).astype(o_ref.dtype)


def decode_attention_stacked(
    q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray, layer: jnp.ndarray,
    lengths: jnp.ndarray, *, block_k: int | None = None,
    scale: float | None = None, interpret: bool = False,
) -> jnp.ndarray:
    """q: (B, H, D); k/v: (L, B, T, K, D); layer: () or (1,) int32;
    lengths: (B,) int32 -> (B, H, D).  Scores are scaled by ``scale``, by
    default D**-0.5."""
    bsz, h, d = q.shape
    t, n_kv = k.shape[2], k.shape[3]
    block_k = block_k or default_block_k(t, n_kv, d, h, k.dtype.itemsize)
    assert h % n_kv == 0, (h, n_kv)
    n_k = -(-t // block_k)
    rows = block_k * n_kv

    def kv_index(b, j, layer_ref, len_ref):
        last = jnp.maximum(len_ref[b] - 1, 0) // block_k
        return layer_ref[0], b, jnp.minimum(j, last), 0, 0

    def fixed(b, j, *_):
        return 0, 0

    scale = 1.0 / math.sqrt(d) if scale is None else scale
    kernel = functools.partial(_decode_kernel, scale=scale,
                               block_k=block_k, n_kv_heads=n_kv,
                               n_kv_blocks=n_k, ragged=t % block_k != 0)
    kv_spec = pl.BlockSpec((None, None, block_k, n_kv, d), kv_index)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(bsz, n_k),
        in_specs=[
            pl.BlockSpec((h, 1), fixed),
            pl.BlockSpec((1, rows), fixed),
            pl.BlockSpec((None, h, d), lambda b, j, *_: (b, 0, 0)),
            kv_spec,
            kv_spec,
        ],
        out_specs=pl.BlockSpec((None, h, d), lambda b, j, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, 1), jnp.float32),
            pltpu.VMEM((h, d), jnp.float32),
        ],
    )
    query_heads = (jnp.arange(h, dtype=jnp.int32) // (h // n_kv))[:, None]
    row_heads = (jnp.arange(rows, dtype=jnp.int32) % n_kv)[None, :]
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bsz, h, d), q.dtype),
        interpret=interpret,
        name=NAME,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), lengths.astype(jnp.int32),
      query_heads, row_heads, q, k, v)
