"""Float32 reference of the first training steps: loss, gradient, AdamW.

AdamW as the configuration states it (``run`` block): gradients clipped to a
global norm, first and second moments with bias correction, decoupled weight
decay on every parameter, and a learning rate warmed up linearly over
``warmup_steps`` then following a cosine to ``total_steps``.  The
parameters are drawn from the seed by the benchmark's own generator; the
batches come from the benchmark's own copy of the token generator.

``precision="bf16"`` is the control: every matrix product rounds its inputs
to bfloat16 and the parameters are held in bfloat16 between steps.
"""

from __future__ import annotations

import math
from typing import Callable, Dict, List, Mapping

import jax
import jax.numpy as jnp
import numpy as np

B1, B2, EPS = 0.9, 0.95, 1e-8


def learning_rate(step: int, run: Mapping) -> float:
    base, warm, total = run["learning_rate"], run["warmup_steps"], \
        run["total_steps"]
    if step < warm:
        return base * (step + 1) / max(warm, 1)
    progress = min(max((step - warm) / max(total - warm, 1), 0.0), 1.0)
    return 0.5 * base * (1.0 + math.cos(math.pi * progress))


def leaf_norms(tree) -> Dict[str, float]:
    """{"a/b/c": float32 norm} of every leaf, computed on the device."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    norms = jax.jit(lambda leaves: [jnp.sqrt(jnp.sum(jnp.square(
        x.astype(jnp.float32)))) for x in leaves])([x for _, x in flat])
    return {path_name(p): float(v) for (p, _), v in zip(flat, norms)}


def path_name(path) -> str:
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in path)


def follow(params0, batches: List[dict], loss_fn: Callable, run: Mapping,
           precision: str = "f32", half_batch: bool = False) -> dict:
    """Take ``len(batches)`` AdamW steps from ``params0`` (float32).

    Returns the loss of each step, the norm of each leaf of the first
    (clipped) gradient, and the norm of each leaf's change after the last
    step.  ``half_batch`` plants a fault: each step sees only the first half
    of its rows and takes the mean over them."""
    store = jnp.bfloat16 if precision == "bf16" else jnp.float32
    params = jax.tree.map(lambda p: p.astype(store), params0)
    mu = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params0)
    nu = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params0)
    clip, wd = run["grad_clip"], run["weight_decay"]

    @jax.jit
    def step(params, mu, nu, tokens, labels, lr, count):
        p32 = jax.tree.map(lambda p: p.astype(jnp.float32), params)
        value, grads = jax.value_and_grad(loss_fn)(p32, tokens, labels)
        gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                             for g in jax.tree.leaves(grads)))
        scale = jnp.minimum(1.0, clip / jnp.maximum(gnorm, 1e-12))
        grads = jax.tree.map(lambda g: g * scale, grads)
        mu = jax.tree.map(lambda m, g: B1 * m + (1 - B1) * g, mu, grads)
        nu = jax.tree.map(lambda v, g: B2 * v + (1 - B2) * g * g, nu, grads)
        c1, c2 = 1 - B1 ** count, 1 - B2 ** count

        def update(p, m, v):
            upd = (m / c1) / (jnp.sqrt(v / c2) + EPS) + wd * p
            return (p - lr * upd).astype(store)

        return jax.tree.map(update, p32, mu, nu), mu, nu, value, grads

    losses, first_grad = [], None
    for i, batch in enumerate(batches):
        tokens, labels = batch["tokens"], batch["labels"]
        if half_batch:
            half = tokens.shape[0] // 2
            tokens, labels = tokens[:half], labels[:half]
        params, mu, nu, value, grads = step(
            params, mu, nu, jnp.asarray(tokens), jnp.asarray(labels),
            jnp.float32(learning_rate(i, run)), jnp.float32(i + 1))
        losses.append(float(value))
        if first_grad is None:
            first_grad = leaf_norms(grads)
        del grads
    delta = leaf_norms(jax.tree.map(
        lambda p, p0: p.astype(jnp.float32) - p0, params, params0))
    return {"loss": losses, "grad": first_grad, "delta": delta}


def worst_leaf_gap(got: Dict[str, float], want: Dict[str, float],
                   keep: List[str]) -> tuple:
    """max over ``keep`` of |got - want| / max(want, median of want over
    ``keep``): (gap, leaf)."""
    floor = float(np.median([want[k] for k in keep]))
    gaps = {k: abs(got[k] - want[k]) / max(want[k], floor) for k in keep}
    leaf = max(gaps, key=gaps.get)
    return gaps[leaf], leaf


def moved_leaves(ref_grad: Dict[str, float]) -> List[str]:
    """Leaves whose reference gradient is not nought to rounding: at least a
    thousandth of the median leaf's."""
    median = float(np.median(list(ref_grad.values())))
    return sorted(k for k, v in ref_grad.items() if v >= 1e-3 * median)


def compare(prog: dict, ref: dict) -> Dict[str, float]:
    """The three numbers a training cell holds to its limits."""
    keep = moved_leaves(ref["grad"])
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(prog["loss"],
                                                       ref["loss"]))
    grad_gap, grad_leaf = worst_leaf_gap(prog["grad"], ref["grad"], keep)
    delta_gap, delta_leaf = worst_leaf_gap(prog["delta"], ref["delta"], keep)
    return {"loss": loss_gap, "grad": grad_gap, "delta": delta_gap,
            "_grad_leaf": grad_leaf, "_delta_leaf": delta_leaf,
            "_leaves_kept": len(keep), "_leaves": len(ref["grad"])}
