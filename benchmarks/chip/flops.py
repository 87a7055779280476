"""Parameters, operations and bytes of each configuration, from its shapes.

Everything here is arithmetic on the sizes in a configuration file's
``model`` block (the program's ``ModelConfig`` fields), so a later change to
the program cannot move the yardstick.  Nothing is read from a compiled
program or from the program's own cost model.

Conventions:

- A multiply-add is 2 operations.  Model operations count what the
  algorithm requires once: no recomputation (remat), no padding rows.
- Training costs 3x the forward pass (forward, and a backward of twice
  the forward's work).
- Causal attention reads, for the query at position i (0-based), i + 1 keys.
- The Mamba-2 SSD layer is counted as its chunked dual form with chunk Q
  (the algorithm of the paper): per chunk the C B^T scores once (one B/C
  group), and per head the masked (Q, Q) product with X, the chunk state
  and the inter-chunk output.
- Weights are bf16 when served; the byte counts take the dtype as given.
"""

from __future__ import annotations

from typing import Mapping


def padded_vocab(m: Mapping) -> int:
    """The stored vocabulary: a multiple of 16 (the program pads to it)."""
    return ((m["vocab"] + 15) // 16) * 16


def _ssm_sizes(m: Mapping):
    d_inner = m.get("ssm_expand", 2) * m["d_model"]
    heads = d_inner // m.get("ssm_head_dim", 64)
    return d_inner, m["ssm_state"], heads, m.get("ssm_head_dim", 64)


def layer_params(m: Mapping) -> int:
    """Parameters of one layer of the stack."""
    d = m["d_model"]
    if m["family"] == "dense":
        hq = m["n_heads"] * m["d_head"]
        hkv = m["n_kv_heads"] * m["d_head"]
        attn = d * hq + 2 * d * hkv + hq * d
        mlp = 3 * d * m["d_ff"]  # SwiGLU: gate, up, down
        return attn + mlp + 2 * d  # + two RMSNorm scales
    if m["family"] == "ssm":
        di, n, nh, _ = _ssm_sizes(m)
        in_proj = d * (2 * di + 2 * n + nh)  # z, x, B, C, dt
        conv = m.get("ssm_conv", 4) * (di + 2 * n)
        return in_proj + conv + 3 * nh + di + di * d + d  # A, D, dt_bias;
        # gated-norm scale; out_proj; pre-norm scale
    raise ValueError(f"no parameter count for family {m['family']!r}")


def embedding_params(m: Mapping) -> int:
    return padded_vocab(m) * m["d_model"]


def head_params(m: Mapping) -> int:
    """Parameters the output head reads (the embedding itself when tied)."""
    return padded_vocab(m) * m["d_model"]


def param_count(m: Mapping) -> int:
    """All stored parameters: the stack, the final norm, the embedding and,
    when it is not tied, the output head."""
    total = m["n_layers"] * layer_params(m) + m["d_model"]
    total += embedding_params(m)
    if not m.get("tie_embeddings", False):
        total += head_params(m)
    return total


def _layer_matmul_params(m: Mapping) -> int:
    """Parameters of one layer that take part in a matrix product."""
    d = m["d_model"]
    if m["family"] == "dense":
        return layer_params(m) - 2 * d
    di, n, nh, _ = _ssm_sizes(m)
    return d * (2 * di + 2 * n + nh) + di * d


def _attention_fwd_per_token(m: Mapping, keys: float) -> float:
    """Scores and weighted sum of one query over ``keys`` keys, all layers."""
    hq = m["n_heads"] * m["d_head"]
    return m["n_layers"] * 4.0 * hq * keys


def _ssd_fwd_per_token(m: Mapping) -> float:
    """The SSD layer's own operations per token, all layers (chunked form),
    plus the depthwise causal convolution."""
    di, n, nh, p = _ssm_sizes(m)
    q = m.get("ssm_chunk", 256)
    scores = 2.0 * q * n  # C_i . B_j over the chunk, shared by the heads
    per_head = 2.0 * q * p + 2.0 * n * p + 2.0 * n * p  # M X, state, C h
    conv = 2.0 * m.get("ssm_conv", 4) * (di + 2 * n)
    return m["n_layers"] * (scores + nh * per_head + conv)


def forward_flops_per_token(m: Mapping, seq: int) -> float:
    """Forward operations per token of a causal sequence of ``seq`` tokens,
    the output head over every position included."""
    matmul = 2.0 * (m["n_layers"] * _layer_matmul_params(m) + head_params(m))
    if m["family"] == "dense":
        return matmul + _attention_fwd_per_token(m, (seq + 1) / 2.0)
    return matmul + _ssd_fwd_per_token(m)


def train_flops_per_token(m: Mapping, seq: int) -> float:
    """Model operations of one training token: forward and backward."""
    return 3.0 * forward_flops_per_token(m, seq)


def prefill_flops(m: Mapping, batch: int, prompt: int) -> float:
    """Prefill of ``batch`` prompts of ``prompt`` tokens: the stack over
    every position, the head over the last one only."""
    stack = 2.0 * m["n_layers"] * _layer_matmul_params(m)
    if m["family"] == "dense":
        per_prompt = prompt * (
            stack + _attention_fwd_per_token(m, (prompt + 1) / 2.0))
    else:
        per_prompt = prompt * (stack + _ssd_fwd_per_token(m))
    return batch * (per_prompt + 2.0 * head_params(m))


def decode_flops(m: Mapping, batch: int, cache_len: int) -> float:
    """One decode step of ``batch`` rows whose new token attends to
    ``cache_len`` positions (its own included)."""
    per_row = 2.0 * (m["n_layers"] * _layer_matmul_params(m) + head_params(m))
    if m["family"] == "dense":
        per_row += _attention_fwd_per_token(m, cache_len)
    return batch * per_row


def decode_bytes(m: Mapping, batch: int, cache_len: int,
                 weight_bytes: int = 2, cache_bytes: int = 2) -> float:
    """Bytes one decode step must move: every weight of the stack, the final
    norm and the head read once; each row's embedding row; the valid KV
    prefix (``cache_len - 1`` positions) read and the new position written."""
    d = m["d_model"]
    weights = (m["n_layers"] * layer_params(m) + d + head_params(m)) \
        * weight_bytes
    embed_rows = batch * d * weight_bytes
    if m["family"] != "dense":
        raise ValueError("decode bytes are defined for the dense family")
    kv_per_pos = 2 * m["n_layers"] * m["n_kv_heads"] * m["d_head"] \
        * cache_bytes
    kv = batch * kv_per_pos * cache_len  # (cache_len - 1) read + 1 written
    return float(weights + embed_rows + kv)


def serve_job_flops(m: Mapping, batch: int, prompt: int,
                    new_tokens: int) -> float:
    """One offline job: prefill, then ``new_tokens - 1`` decode steps (the
    first token comes from the prefill's logits)."""
    total = prefill_flops(m, batch, prompt)
    for i in range(new_tokens - 1):
        total += decode_flops(m, batch, prompt + i + 1)
    return total


def serve_job_decode_bytes(m: Mapping, batch: int, prompt: int,
                           new_tokens: int) -> float:
    """Mean bytes per decode step over one job's ``new_tokens - 1`` steps."""
    steps = [decode_bytes(m, batch, prompt + i + 1)
             for i in range(new_tokens - 1)]
    return sum(steps) / len(steps)
