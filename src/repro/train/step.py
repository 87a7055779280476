"""Train / eval steps with optional gradient accumulation (microbatching).

``make_train_step`` closes over the configs so the jitted signature is
``(state, batch) -> (state, metrics)`` — the function the dry-run lowers.
The head projection and the loss run under the named scope ``head_loss``,
which the compiled program's ``op_name`` metadata carries.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, RunConfig
from repro.models import forward_train
from repro.optim import adamw_update, cosine_schedule
from repro.train.loss import cross_entropy_loss
from repro.train.state import TrainState


def _loss_fn(params, cfg: ModelConfig, run: RunConfig, batch):
    hidden, extras = forward_train(params, cfg, run, batch["tokens"],
                                   frontend=batch.get("frontend"))
    labels = batch["labels"]
    if hidden.shape[1] != labels.shape[1]:  # vlm: frontend positions unsupervised
        hidden = hidden[:, hidden.shape[1] - labels.shape[1]:]
    with jax.named_scope("head_loss"):
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        loss, acc = cross_entropy_loss(hidden, head, labels,
                                       chunk=run.loss_chunk, vocab=cfg.vocab)
    aux = extras.get("aux", jnp.zeros((), jnp.float32))
    total = loss + 0.01 * aux
    return total, {"loss": loss, "aux": aux, "accuracy": acc}


def _grads(params, cfg, run, batch):
    grad_fn = jax.value_and_grad(_loss_fn, has_aux=True)
    (loss, metrics), grads = grad_fn(params, cfg, run, batch)
    return loss, metrics, grads


def train_step(state: TrainState, batch: Dict[str, jnp.ndarray],
               cfg: ModelConfig, run: RunConfig) -> Tuple[TrainState, Dict]:
    if run.microbatch > 1:
        mb = run.microbatch
        b = batch["tokens"].shape[0]
        assert b % mb == 0, f"batch {b} % microbatch {mb} != 0"

        def split(x):
            return x.reshape(mb, b // mb, *x.shape[1:])

        micro = {k: split(v) for k, v in batch.items()}

        def body(carry, mbatch):
            acc_grads, acc_metrics = carry
            _, metrics, grads = _grads(state.params, cfg, run, mbatch)
            acc_grads = jax.tree.map(
                lambda a, g: a + g.astype(jnp.float32) / mb, acc_grads, grads)
            acc_metrics = jax.tree.map(
                lambda a, m: a + m / mb, acc_metrics, metrics)
            return (acc_grads, acc_metrics), None

        zero_grads = jax.tree.map(
            lambda p: jnp.zeros(p.shape, jnp.float32), state.params)
        zero_metrics = {"loss": jnp.zeros((), jnp.float32),
                        "aux": jnp.zeros((), jnp.float32),
                        "accuracy": jnp.zeros((), jnp.float32)}
        (grads, metrics), _ = jax.lax.scan(body, (zero_grads, zero_metrics), micro)
    else:
        _, metrics, grads = _grads(state.params, cfg, run, batch)

    if run.grad_compression == "int8":
        # Simulated compressed DP gradient exchange: symmetric int8 per
        # tensor (16x wire format).  On a real pod this wraps the cross-pod
        # reduction; here it quantizes the accumulated gradients so the
        # optimizer sees exactly what a compressed sync would deliver.
        from repro.optim.adamw import compress_int8, decompress_int8

        def _roundtrip(g):
            if g.ndim == 0:
                return g
            q, scale = compress_int8(g.astype(jnp.float32))
            return decompress_int8(q, scale)

        grads = jax.tree.map(_roundtrip, grads)

    lr = cosine_schedule(state.step, run.learning_rate, run.warmup_steps,
                         run.total_steps)
    new_params, new_opt, opt_metrics = adamw_update(
        state.params, grads, state.opt, lr,
        weight_decay=run.weight_decay, grad_clip=run.grad_clip)
    metrics = {**metrics, **opt_metrics}
    return TrainState(params=new_params, opt=new_opt, step=state.step + 1), metrics


def eval_step(state: TrainState, batch, cfg: ModelConfig, run: RunConfig):
    _, metrics = _loss_fn(state.params, cfg, run, batch)
    return metrics


def make_train_step(cfg: ModelConfig, run: RunConfig):
    step = functools.partial(train_step, cfg=cfg, run=run)
    step.__name__ = "train_step"  # its jit's program is ``jit_train_step``
    return step
