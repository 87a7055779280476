"""The ssm family: a Mamba-2 language model (arXiv:2405.21060).

What the harness knows of the family: its parameter leaves (``leaves``),
its counts over the stack (``stack_params`` ... ``decode_state_bytes``,
which ``flops.py`` adds to the embedding and head), its float32 reference
(``loss``, as the sequential state recurrence) and its CPU sizes
(``TINY``).

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

Weights follow the published initialisation: the depthwise convolution
U(-1/sqrt(K), 1/sqrt(K)), A = -U(1, 16) (``A_log`` = log), dt from
log-uniform [1e-3, 1e-1] stored as its inverse softplus (``dt_bias``),
D = 1.

Operations count the SSD layer as its chunked dual form with chunk Q (the
algorithm of the paper): per chunk the C B^T scores once (one B/C group),
and per head the masked (Q, Q) product with X, the chunk state and the
inter-chunk output.
"""

from __future__ import annotations

from typing import Mapping

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip.reference.numerics import (
    HIGHEST, masked_logits, matmul, rms_norm, silu)
from benchmarks.chip.weights import Leaf

TIME_BLOCK = 64  # steps rematerialised together in the backward pass

# Widths and lengths small enough for the CPU rehearsal.
TINY = {"n_layers": 2, "d_model": 512, "vocab": 1000, "ssm_state": 16,
        "ssm_head_dim": 32, "ssm_chunk": 32}


def _sizes(m: Mapping):
    """Inner width, state size N, heads, head width P."""
    p = m.get("ssm_head_dim", 64)
    d_inner = m.get("ssm_expand", 2) * m["d_model"]
    return d_inner, m["ssm_state"], d_inner // p, p


def _a_log(key, shape):
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))


def _dt_bias(key, shape):
    dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                    np.log(1e-3), np.log(1e-1)))
    return dt + jnp.log(-jnp.expm1(-dt))  # inverse softplus


def leaves(m: Mapping) -> dict:
    """Each layer's leaves, stacked ``n_layers`` deep; ``in_proj`` gives
    (z, x, B, C, dt)."""
    d = m["d_model"]
    di, n, nh, _ = _sizes(m)
    k = m.get("ssm_conv", 4)
    return {
        ("layers", "mamba", "in_proj"):
            Leaf((d, 2 * di + 2 * n + nh), ("normal", 0.02)),
        ("layers", "mamba", "conv_w"):
            Leaf((k, di + 2 * n), ("uniform", k ** -0.5)),
        ("layers", "mamba", "A_log"): Leaf((nh,), _a_log),
        ("layers", "mamba", "D"): Leaf((nh,), ("ones",)),
        ("layers", "mamba", "dt_bias"): Leaf((nh,), _dt_bias),
        ("layers", "mamba", "ssm_norm"): Leaf((di,), ("scale",)),
        ("layers", "mamba", "out_proj"): Leaf((di, d), ("normal", 0.02)),
        ("layers", "norm1"): Leaf((d,), ("scale",)),
    }


def stack_params(m: Mapping) -> int:
    """Parameters of every layer."""
    d = m["d_model"]
    di, n, nh, _ = _sizes(m)
    in_proj = d * (2 * di + 2 * n + nh)  # z, x, B, C, dt
    conv = m.get("ssm_conv", 4) * (di + 2 * n)
    # A, D, dt_bias; gated-norm scale; out_proj; pre-norm scale
    return m["n_layers"] * (in_proj + conv + 3 * nh + di + di * d + d)


def matmul_params(m: Mapping) -> int:
    """Parameters a token reads in matrix products, every layer: the input
    and output projections."""
    d = m["d_model"]
    di, n, nh, _ = _sizes(m)
    return m["n_layers"] * (d * (2 * di + 2 * n + nh) + di * d)


def mixer_flops(m: Mapping, keys: float) -> float:
    """The SSD layer's own operations per token, all layers (chunked form),
    plus the depthwise causal convolution; the same over any ``keys``."""
    di, n, nh, p = _sizes(m)
    q = m.get("ssm_chunk", 256)
    scores = 2.0 * q * n  # C_i . B_j over the chunk, shared by the heads
    per_head = 2.0 * q * p + 2.0 * n * p + 2.0 * n * p  # M X, state, C h
    conv = 2.0 * m.get("ssm_conv", 4) * (di + 2 * n)
    return m["n_layers"] * (scores + nh * per_head + conv)


def decode_mixer_flops(m: Mapping, cache_len: int) -> float:
    """Not counted yet: a decode step counts the projections and the head
    alone, not the recurrent step's own operations."""
    return 0.0


def decode_state_bytes(m: Mapping, cache_len: int, cache_bytes: int) -> int:
    """Not defined yet (the recurrent state a decode row reads): raises."""
    raise ValueError("decode bytes are not defined for the ssm family")


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
    b, s, _ = x.shape
    di, n, nh, p = _sizes(m)
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
