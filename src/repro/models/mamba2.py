"""Mamba-2 (SSD — state-space duality, arXiv:2405.21060) block.

Training/prefill uses the chunked dual form: quadratic attention-like
computation inside chunks of length Q plus a sequential inter-chunk state
recurrence — the loop-carried dependency the HLO LCD analysis surfaces.
Decode is the O(1)-state recurrence.  The intra-chunk computation has a
Pallas kernel counterpart (`repro.kernels.ssd_scan`) validated against this
reference.

B and C come in ``cfg.ssm_groups`` groups (Zamba2-7B: 2), head h reading
group h // (heads / groups), and the gated RMSNorm runs over each group's
channels; the depthwise convolution has a bias where ``cfg.ssm_conv_bias``.
With one group every path computes, bit for bit, what it did before
groups existed.
"""

from __future__ import annotations

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.distributed import constrain
from repro.models.layers import DATA, MODEL, rms_norm


def init_mamba_params(key, cfg, layer_count, dtype) -> Dict[str, jnp.ndarray]:
    """Stacked Mamba-2 block params with leading ``layer_count`` dims."""
    d, di, nh = cfg.d_model, cfg.d_inner, cfg.ssm_heads
    gn = cfg.ssm_groups * cfg.ssm_state  # B (and C) of every group
    conv_ch = di + 2 * gn
    keys = jax.random.split(key, 6)
    scale = 0.02
    proj_out = 2 * di + 2 * gn + nh  # z, x, B, C, dt
    params = {
        "in_proj": jax.random.normal(keys[0], (*layer_count, d, proj_out), dtype) * scale,
        "conv_w": jax.random.normal(keys[1], (*layer_count, cfg.ssm_conv, conv_ch), dtype) * scale,
        "A_log": jnp.zeros((*layer_count, nh), dtype),
        "D": jnp.ones((*layer_count, nh), dtype),
        "dt_bias": jnp.zeros((*layer_count, nh), dtype),
        "ssm_norm": jnp.ones((*layer_count, di), dtype),
        "out_proj": jax.random.normal(keys[2], (*layer_count, di, d), dtype) * scale,
    }
    if cfg.ssm_conv_bias:
        params["conv_b"] = jnp.zeros((*layer_count, conv_ch), dtype)
    return params


def _causal_conv(x: jnp.ndarray, w: jnp.ndarray,
                 state: Optional[jnp.ndarray] = None,
                 bias: Optional[jnp.ndarray] = None
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Depthwise causal conv1d via shifted adds, then SiLU.  x: (B,S,C);
    w: (K,C); bias: (C,) or None.

    ``state``: (B, K-1, C) trailing context from the previous segment.
    Returns (y, new_state)."""
    k = w.shape[0]
    b, s, c = x.shape
    if state is None:
        state = jnp.zeros((b, k - 1, c), x.dtype)
    xp = jnp.concatenate([state, x], axis=1)  # (B, S+K-1, C)
    y = jnp.zeros_like(x)
    for i in range(k):
        y = y + xp[:, i:i + s, :] * w[i]
    if bias is not None:
        y = y + bias
    new_state = xp[:, -(k - 1):, :] if k > 1 else state
    return jax.nn.silu(y), new_state


def _split_proj(proj: jnp.ndarray, cfg):
    di, nh = cfg.d_inner, cfg.ssm_heads
    gn = cfg.ssm_groups * cfg.ssm_state
    z = proj[..., :di]
    xbc = proj[..., di:di + di + 2 * gn]
    dt = proj[..., -nh:]
    return z, xbc, dt


def _per_head(v: jnp.ndarray, groups: int, heads: int) -> jnp.ndarray:
    """(..., G*N) of G groups -> (..., H, N): head h reads group
    h // (H / G)."""
    v = v.reshape(*v.shape[:-1], groups, v.shape[-1] // groups)
    return jnp.repeat(v, heads // groups, axis=-2)


def ssd_grouped(x, dt, A, Bm, Cm, chunk, groups, h0=None,
                chunk_shard=False):
    """``ssd_chunked`` where B and C come in ``groups`` groups, Bm/Cm:
    (B,S,G*N): the heads, in G equal runs, each read their own group's
    (the scan mapped over the groups; with one group, ``ssd_chunked``
    itself: mapped over one group, the train step's gradients round
    differently)."""
    if groups == 1:
        return ssd_chunked(x, dt, A, Bm, Cm, chunk, h0,
                           chunk_shard=chunk_shard)
    b, s, nh, p = x.shape

    def split(v, axis):  # the head (or B/C) axis -> (G, per group)
        return v.reshape(*v.shape[:axis], groups, -1, *v.shape[axis + 1:])

    y, h = jax.vmap(
        lambda x, dt, a, bm, cm, h0: ssd_chunked(
            x, dt, a, bm, cm, chunk, h0, chunk_shard=chunk_shard),
        in_axes=(2, 2, 0, 2, 2, None if h0 is None else 1),
        out_axes=(2, 1))(
            split(x, 2), split(dt, 2), split(A, 0), split(Bm, 2),
            split(Cm, 2), None if h0 is None else split(h0, 1))
    return y.reshape(b, s, nh, p), h.reshape(b, nh, *h.shape[3:])


def ssd_chunked(
    x: jnp.ndarray, dt: jnp.ndarray, A: jnp.ndarray,
    Bm: jnp.ndarray, Cm: jnp.ndarray,
    chunk: int, h0: Optional[jnp.ndarray] = None,
    head_block: int = 4,
    chunk_shard: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Chunked SSD scan (reference; pure jnp).

    x: (B,S,H,P)  dt: (B,S,H)  A: (H,)  Bm/Cm: (B,S,N)
    h0: optional initial state (B,H,N,P).
    Returns (y (B,S,H,P), final state (B,H,N,P)).

    The per-head decay tensor (B,NC,Q,Q,H) is the memory hot-spot of the
    dual form; heads are processed in blocks of ``head_block`` (mirroring the
    Pallas kernel's per-head grid) so the peak is (B,NC,Q,Q,head_block).

    Everything here runs under the named scope ``ssd``, which the compiled
    program's ``op_name`` metadata carries (forward, backward and remat).
    """
    with jax.named_scope("ssd"):
        return _ssd_chunked(x, dt, A, Bm, Cm, chunk, h0, head_block,
                            chunk_shard)


def _ssd_chunked(x, dt, A, Bm, Cm, chunk, h0, head_block, chunk_shard):
    b, s, nh, p = x.shape
    n = Bm.shape[-1]
    q = min(chunk, s)
    if s % q != 0:
        # Right-pad to a chunk multiple: dt=0 there => decay 1, contribution
        # 0, so the final state equals the state after the s real steps.
        pad = q - s % q
        y, h_last = _ssd_chunked(
            jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0))),
            jnp.pad(dt, ((0, 0), (0, pad), (0, 0))),
            A,
            jnp.pad(Bm, ((0, 0), (0, pad), (0, 0))),
            jnp.pad(Cm, ((0, 0), (0, pad), (0, 0))),
            chunk, h0, head_block, chunk_shard,
        )
        return y[:, :s], h_last
    nc = s // q

    xc = x.reshape(b, nc, q, nh, p)
    dtc = dt.reshape(b, nc, q, nh).astype(jnp.float32)
    bc = Bm.reshape(b, nc, q, n)
    cc = Cm.reshape(b, nc, q, n)

    dA = dtc * A.astype(jnp.float32)  # (B,nc,Q,H), negative
    cum = jnp.cumsum(dA, axis=2)  # inclusive cumulative log-decay
    xdt = xc.astype(jnp.float32) * dtc[..., None]  # (B,nc,Q,H,P) f32

    if chunk_shard:
        # The intra-chunk dual form is chunk-parallel: shard the chunk dim
        # over the model axis so the (Q,Q,head) decay tensors divide by it.
        cum = constrain(cum, DATA, MODEL, None, None)
        xdt = constrain(xdt, DATA, MODEL, None, None, None)
        bc = constrain(bc, DATA, MODEL, None, None)
        cc = constrain(cc, DATA, MODEL, None, None)

    scores = jnp.einsum("bcin,bcjn->bcij", cc, bc,
                        preferred_element_type=jnp.float32)  # (B,nc,Q,Q)
    if chunk_shard:
        scores = constrain(scores, DATA, MODEL, None, None)
    tri = jnp.tril(jnp.ones((q, q), jnp.float32))

    hb = 1
    for cand in range(min(head_block, nh), 0, -1):
        if nh % cand == 0:
            hb = cand
            break
    nb = nh // hb
    cum_b = jnp.moveaxis(cum.reshape(b, nc, q, nb, hb), 3, 0)  # (nb,b,nc,Q,hb)
    xdt_b = jnp.moveaxis(xdt.reshape(b, nc, q, nb, hb, p), 3, 0)

    def per_block(args):
        cum_h, xdt_h = args  # (b,nc,Q,hb), (b,nc,Q,hb,p)
        # Mask the exponent BEFORE exp (double-where): the upper triangle has
        # cum_i - cum_j > 0 growing with chunk length, so exp() overflows to
        # inf there and inf * tri(=0) poisons fwd/bwd with NaNs.
        diff = cum_h[:, :, :, None, :] - cum_h[:, :, None, :, :]
        valid = tri[None, None, :, :, None] > 0
        decay = jnp.where(valid, jnp.exp(jnp.where(valid, diff, 0.0)), 0.0)
        m = scores[..., None] * decay
        y = jnp.einsum("bcijh,bcjhp->bcihp", m, xdt_h)
        d2e = jnp.exp(cum_h[:, :, -1:, :] - cum_h)  # (b,nc,Q,hb)
        st = jnp.einsum("bcjn,bcjh,bcjhp->bchnp", bc.astype(jnp.float32),
                        d2e, xdt_h)
        return y, st

    y_b, st_b = jax.lax.map(per_block, (cum_b, xdt_b))
    y_intra = jnp.moveaxis(y_b, 0, 3).reshape(b, nc, q, nh, p)
    chunk_states = jnp.moveaxis(st_b, 0, 2).reshape(b, nc, nh, n, p)
    if chunk_shard:
        y_intra = constrain(y_intra, DATA, MODEL, None, None, None)
        chunk_states = constrain(chunk_states, DATA, MODEL, None, None, None)
    chunk_decay = jnp.exp(cum[:, :, -1, :])  # (B,nc,H)

    def body(h_prev, inputs):
        cdecay, cstate = inputs  # (B,H), (B,H,N,P)
        h_new = cdecay[..., None, None] * h_prev + cstate
        return h_new, h_prev

    h_init = (jnp.zeros((b, nh, n, p), jnp.float32) if h0 is None
              else h0.astype(jnp.float32))
    h_last, h_prevs = jax.lax.scan(
        body, h_init,
        (jnp.moveaxis(chunk_decay, 1, 0), jnp.moveaxis(chunk_states, 1, 0)),
    )
    h_prevs = jnp.moveaxis(h_prevs, 0, 1)  # (B,nc,H,N,P)

    y_inter = jnp.einsum("bcin,bcih,bchnp->bcihp", cc.astype(jnp.float32),
                         jnp.exp(cum), h_prevs)
    y = (y_intra + y_inter).reshape(b, s, nh, p)
    return y.astype(x.dtype), h_last.astype(x.dtype)


def mamba_block(
    params: Dict[str, jnp.ndarray],
    x: jnp.ndarray,
    cfg,
    ssm_state: Optional[jnp.ndarray] = None,
    conv_state: Optional[jnp.ndarray] = None,
    single_step: bool = False,
    chunk_shard: bool = False,
) -> Tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """Mamba-2 block.  x: (B,S,d) -> (y, ssm_state, conv_state).

    ``single_step=True`` runs the O(1) decode recurrence (S must be 1).
    ``chunk_shard`` keeps the whole block sequence-sharded over the model
    axis (in_proj/conv activations divide by it; the causal conv's halo
    exchange becomes a collective-permute) — §Perf iterations 1 & 5.
    """
    b, s, _ = x.shape
    di, n, nh, p = cfg.d_inner, cfg.ssm_state, cfg.ssm_heads, cfg.ssm_head_dim
    groups = cfg.ssm_groups
    gn = groups * n

    proj = x @ params["in_proj"]
    if chunk_shard and not single_step:
        proj = constrain(proj, DATA, MODEL, None)
    else:
        proj = constrain(proj, DATA, None, MODEL)
    z, xbc, dt_raw = _split_proj(proj, cfg)
    xbc, conv_state = _causal_conv(xbc, params["conv_w"], conv_state,
                                   params.get("conv_b"))
    xs = xbc[..., :di].reshape(b, s, nh, p)
    Bm = xbc[..., di:di + gn]
    Cm = xbc[..., di + gn:]
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32)
                         + params["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(params["A_log"].astype(jnp.float32))

    if single_step:
        dA = jnp.exp(dt[:, 0] * A)  # (B,H)
        h_prev = (jnp.zeros((b, nh, n, p), jnp.float32) if ssm_state is None
                  else ssm_state.astype(jnp.float32))
        xdt = xs[:, 0].astype(jnp.float32) * dt[:, 0][..., None]  # (B,H,P)
        bm, cm = Bm[:, 0].astype(jnp.float32), Cm[:, 0].astype(jnp.float32)
        bm, cm = _per_head(bm, groups, nh), _per_head(cm, groups, nh)
        h_new = dA[..., None, None] * h_prev \
            + bm[..., None] * xdt[:, :, None, :]
        y = jnp.einsum("bhn,bhnp->bhp", cm, h_new)
        y = y[:, None].astype(x.dtype)  # (B,1,H,P)
        ssm_state = h_new.astype(x.dtype)
    else:
        y, ssm_state = ssd_grouped(xs, dt, A, Bm, Cm, cfg.ssm_chunk, groups,
                                   ssm_state, chunk_shard=chunk_shard)

    y = y + params["D"].astype(x.dtype)[None, None, :, None] * xs
    y = y.reshape(b, s, di)
    # the gated norm runs over each group's d_inner / G channels
    y = rms_norm((y * jax.nn.silu(z)).reshape(b, s, groups, -1),
                 params["ssm_norm"].reshape(groups, -1),
                 cfg.norm_eps).reshape(b, s, di)
    out = y @ params["out_proj"]
    return constrain(out, DATA, None, None), ssm_state, conv_state
