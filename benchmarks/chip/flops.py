"""Parameters, operations and bytes of each configuration, from its shapes.

Everything here is arithmetic on the sizes in a configuration file's
``model`` block (the program's ``ModelConfig`` fields), so a later change to
the program cannot move the yardstick.  Nothing is read from a compiled
program or from the program's own cost model.

What differs by family is the family file's (``reference/<family>.py``,
found by ``harness.family``), each summed over the whole stack, so that a
family whose layers differ (leading dense layers, shared blocks, routed
experts) gives its own sums: ``stack_params``, the parameters of its
leaves; ``matmul_params``, those a token reads in matrix products;
``mixer_flops`` and ``decode_mixer_flops``, the sequence mixer's
operations per token; ``decode_state_bytes``, the state a decode row reads
and writes.  What is shared (the padded vocabulary, the embedding and the
head, training's 3x, the job totals) is here.

Conventions:

- A multiply-add is 2 operations.  Model operations count what the
  algorithm requires once: no recomputation (remat), no padding rows.
- Training costs 3x the forward pass (forward, and a backward of twice
  the forward's work).
- Causal attention reads, for the query at position i (0-based), i + 1 keys.
- Weights are bf16 when served; the byte counts take the dtype as given.
"""

from __future__ import annotations

from typing import Mapping

from benchmarks.chip import harness


def padded_vocab(m: Mapping) -> int:
    """The stored vocabulary: a multiple of 16 (the program pads to it)."""
    return ((m["vocab"] + 15) // 16) * 16


def layer_params(m: Mapping) -> int:
    """Parameters of one layer of the stack, where every layer is alike."""
    return harness.family(m).stack_params(m) // m["n_layers"]


def embedding_params(m: Mapping) -> int:
    return padded_vocab(m) * m["d_model"]


def head_params(m: Mapping) -> int:
    """Parameters the output head reads (the embedding itself when tied)."""
    return padded_vocab(m) * m["d_model"]


def param_count(m: Mapping) -> int:
    """All stored parameters: the stack, the final norm, the embedding and,
    when it is not tied, the output head."""
    total = harness.family(m).stack_params(m) + m["d_model"]
    total += embedding_params(m)
    if not m.get("tie_embeddings", False):
        total += head_params(m)
    return total


def forward_flops_per_token(m: Mapping, seq: int) -> float:
    """Forward operations per token of a causal sequence of ``seq`` tokens,
    the output head over every position included."""
    family = harness.family(m)
    matmul = 2.0 * (family.matmul_params(m) + head_params(m))
    return matmul + family.mixer_flops(m, (seq + 1) / 2.0)


def train_flops_per_token(m: Mapping, seq: int) -> float:
    """Model operations of one training token: forward and backward."""
    return 3.0 * forward_flops_per_token(m, seq)


def prefill_flops(m: Mapping, batch: int, prompt: int) -> float:
    """Prefill of ``batch`` prompts of ``prompt`` tokens: the stack over
    every position, the head over the last one only."""
    family = harness.family(m)
    stack = 2.0 * family.matmul_params(m)
    per_prompt = prompt * (stack + family.mixer_flops(m, (prompt + 1) / 2.0))
    return batch * (per_prompt + 2.0 * head_params(m))


def decode_flops(m: Mapping, batch: int, cache_len: int) -> float:
    """One decode step of ``batch`` rows whose new token attends to
    ``cache_len`` positions (its own included)."""
    family = harness.family(m)
    per_row = 2.0 * (family.matmul_params(m) + head_params(m))
    per_row += family.decode_mixer_flops(m, cache_len)
    return batch * per_row


def decode_bytes(m: Mapping, batch: int, cache_len: int,
                 weight_bytes: int = 2, cache_bytes: int = 2) -> float:
    """Bytes one decode step must move: every weight of the stack, the final
    norm and the head read once; each row's embedding row; each row's state
    at ``cache_len`` (the family's ``decode_state_bytes``)."""
    d, family = m["d_model"], harness.family(m)
    weights = (family.stack_params(m) + d + head_params(m)) * weight_bytes
    embed_rows = batch * d * weight_bytes
    state = batch * family.decode_state_bytes(m, cache_len, cache_bytes)
    return float(weights + embed_rows + state)


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
