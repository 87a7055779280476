"""Mamba-2 SSD intra-chunk kernel (Pallas TPU).

Computes, for one (batch, chunk, head) grid cell, the chunk-diagonal output
block and the chunk's summary state:

    Y_intra[i] = sum_{j<=i} (C_i . B_j) exp(cum_i - cum_j) * xdt_j
    S_chunk    = sum_j B_j^T (exp(cum_last - cum_j) * xdt_j)

The sequential inter-chunk recurrence (the LCD the paper's analysis flags)
stays outside in jnp — it is O(n_chunks) with tiny state and does not
benefit from a kernel.

Layouts (already split per head by the wrapper):
  xdt (B, NC, H, Q, P)   dt-scaled inputs
  cum (B, NC, H, Q)      inclusive cumulative log-decay (passed to the
                         kernel as a (Q, 1) column and a (1, Q) row)
  Bm/Cm (B, NC, Q, N)    shared across heads (single B/C group)
Outputs: y (B, NC, H, Q, P), states (B, NC, H, N, P).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _ssd_kernel(xdt_ref, cum_col_ref, cum_row_ref, b_ref, c_ref, y_ref,
                state_ref):
    xdt = xdt_ref[0, 0, 0].astype(jnp.float32)  # (Q, P)
    cum_c = cum_col_ref[0, 0, 0].astype(jnp.float32)  # (Q, 1)
    cum_r = cum_row_ref[0, 0, 0].astype(jnp.float32)  # (1, Q)
    bm = b_ref[0, 0].astype(jnp.float32)  # (Q, N)
    cm = c_ref[0, 0].astype(jnp.float32)  # (Q, N)
    q = xdt.shape[0]

    scores = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)  # (Q,Q)
    ii = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    valid = ii >= jj
    # Mask the exponent before exp: the upper triangle overflows to inf for
    # long chunks (same guard as the jnp reference).
    decay = jnp.exp(jnp.where(valid, cum_c - cum_r, 0.0))
    m = jnp.where(valid, scores * decay, 0.0)
    y_ref[0, 0, 0] = jax.lax.dot_general(
        m, xdt, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32).astype(y_ref.dtype)

    # cum at the chunk's last position, as a (1, 1) masked lane reduction.
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, q), 1)
    cum_last = jnp.sum(jnp.where(lane == q - 1, cum_r, 0.0), axis=1,
                       keepdims=True)
    decay_to_end = jnp.exp(cum_last - cum_c)  # (Q, 1)
    state = jax.lax.dot_general(
        bm, xdt * decay_to_end, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)  # (N, P)
    state_ref[0, 0, 0] = state.astype(state_ref.dtype)


def ssd_intra_chunk(
    xdt: jnp.ndarray, cum: jnp.ndarray, bm: jnp.ndarray, cm: jnp.ndarray,
    *, interpret: bool = False,
):
    """xdt (B,NC,H,Q,P), cum (B,NC,H,Q), bm/cm (B,NC,Q,N) ->
    (y (B,NC,H,Q,P) f32, states (B,NC,H,N,P) f32)."""
    b, nc, h, q, p = xdt.shape
    n = bm.shape[-1]
    # cum goes in as a column and as a row: the TPU tiles a block's last two
    # dims, and both orientations are needed for the (Q, Q) decay.
    cum_col = cum[..., None]  # (B,NC,H,Q,1)
    cum_row = cum[..., None, :]  # (B,NC,H,1,Q)

    return pl.pallas_call(
        _ssd_kernel,
        grid=(b, nc, h),
        in_specs=[
            pl.BlockSpec((1, 1, 1, q, p), lambda bi, ci, hi: (bi, ci, hi, 0, 0)),
            pl.BlockSpec((1, 1, 1, q, 1), lambda bi, ci, hi: (bi, ci, hi, 0, 0)),
            pl.BlockSpec((1, 1, 1, 1, q), lambda bi, ci, hi: (bi, ci, hi, 0, 0)),
            pl.BlockSpec((1, 1, q, n), lambda bi, ci, hi: (bi, ci, 0, 0)),
            pl.BlockSpec((1, 1, q, n), lambda bi, ci, hi: (bi, ci, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, 1, q, p), lambda bi, ci, hi: (bi, ci, hi, 0, 0)),
            pl.BlockSpec((1, 1, 1, n, p), lambda bi, ci, hi: (bi, ci, hi, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b, nc, h, q, p), jnp.float32),
            jax.ShapeDtypeStruct((b, nc, h, n, p), jnp.float32),
        ],
        interpret=interpret,
    )(xdt, cum_col, cum_row, bm, cm)
