"""Float32 reference of a Mamba-2 language model (arXiv:2405.21060), as the
sequential state recurrence.

Each layer: RMSNorm; one input projection to (z, x, B, C, dt); a depthwise
causal convolution of width K over (x, B, C) followed by SiLU (no
convolution bias: the program has none, a departure from the published
block); dt = softplus(dt + dt_bias); A = -exp(A_log); then, per time step t
and head h, with one B/C group shared by the heads,

    h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t^T      (N x P state per head)
    y_t = C_t^T h_t + D x_t

then the gated RMSNorm of y * silu(z) and the output projection, added to
the residual.  Embeddings are tied; a final RMSNorm precedes the head.  The
recurrence runs one step at a time (``lax.scan`` over time, rematerialised
in blocks of steps for the backward pass), independent of the chunked dual
form the program computes.
"""

from __future__ import annotations

from typing import Mapping

import jax
import jax.numpy as jnp

from benchmarks.chip.reference.numerics import (
    HIGHEST, masked_logits, matmul, rms_norm, silu)

TIME_BLOCK = 64  # steps rematerialised together in the backward pass


def _recurrence(xs, dt, a, bm, cm):
    """xs (B,S,H,P), dt (B,S,H), a (H,), bm/cm (B,S,N) -> y (B,S,H,P)."""
    b, s, nh, p = xs.shape
    n = bm.shape[-1]

    def step(state, inp):
        x_t, dt_t, b_t, c_t = inp
        state = jnp.exp(dt_t * a)[:, :, None, None] * state \
            + b_t[:, None, :, None] * (x_t * dt_t[..., None])[:, :, None, :]
        y_t = jnp.einsum("bn,bhnp->bhp", c_t, state, precision=HIGHEST)
        return state, y_t

    @jax.checkpoint
    def block(state, inp):
        return jax.lax.scan(step, state, inp)

    blk = TIME_BLOCK if s % TIME_BLOCK == 0 else s

    def to_blocks(v):  # (B, S, ...) -> (S/blk, blk, B, ...)
        v = jnp.moveaxis(v, 1, 0)
        return v.reshape(s // blk, blk, *v.shape[1:])

    inputs = tuple(to_blocks(v) for v in (xs, dt, bm, cm))
    state0 = jnp.zeros((b, nh, n, p), jnp.float32)
    _, ys = jax.lax.scan(block, state0, inputs)
    return jnp.moveaxis(ys.reshape(s, b, nh, p), 0, 1)


def layer(lp, x, m: Mapping, precision: str = "f32"):
    b, s, d = x.shape
    di = m["ssm_expand"] * d
    n, p = m["ssm_state"], m["ssm_head_dim"]
    nh = di // p
    mp = lp["mamba"]
    h = rms_norm(x, lp["norm1"], m["norm_eps"])
    proj = matmul(h, mp["in_proj"], precision)
    z, xbc, dt = proj[..., :di], proj[..., di:2 * di + 2 * n], \
        proj[..., 2 * di + 2 * n:]
    k = mp["conv_w"].shape[0]
    padded = jnp.pad(xbc, ((0, 0), (k - 1, 0), (0, 0)))
    xbc = silu(sum(mp["conv_w"][i] * padded[:, i:i + s] for i in range(k)))
    xs = xbc[..., :di].reshape(b, s, nh, p)
    bm, cm = xbc[..., di:di + n], xbc[..., di + n:]
    dt = jax.nn.softplus(dt + mp["dt_bias"])
    y = _recurrence(xs, dt, -jnp.exp(mp["A_log"]), bm, cm)
    y = (y + mp["D"][:, None] * xs).reshape(b, s, di)
    y = rms_norm(y * silu(z), mp["ssm_norm"], m["norm_eps"])
    return x + matmul(y, mp["out_proj"], precision)


def loss(params, tokens, labels, m: Mapping, precision: str = "f32"):
    """Mean next-token cross entropy over every position of every row."""
    x = jnp.take(params["embed"], tokens, axis=0)

    @jax.checkpoint
    def body(x, lp):
        return layer(lp, x, m, precision), None

    x, _ = jax.lax.scan(body, x, params["layers"])
    x = rms_norm(x, params["final_norm"], m["norm_eps"])

    @jax.checkpoint
    def row_loss(carry, row):
        h, lab = row
        logits = masked_logits(h, params["embed"].T, m["vocab"], precision)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, lab[:, None], axis=-1)[:, 0]
        return carry + jnp.sum(lse - picked), None

    total, _ = jax.lax.scan(row_loss, jnp.zeros((), jnp.float32), (x, labels))
    return total / labels.size
