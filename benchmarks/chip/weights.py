"""Weights from the seed, in the program's parameter layout.

Every leaf is drawn from its own key, ``fold_in(fold_in(root, leaf), layer)``,
so one layer's slice can be drawn again alone: the program gets the whole
tree from one jitted call (``make_params``), and a reference draws layer by
layer (``layer_params``) the same numbers without holding the whole model
and without taking anything from the program.

Distributions follow the published initialisations where they matter to
the numerics: matrices N(0, 0.02^2); RMSNorm scales 1 + N(0, 0.1^2), so that
a scale applied wrongly shows; the query and key projections
N(0, QK_SPREAD / d_model), so that attention scores spread by ~QK_SPREAD as
a trained model's heads do (at 0.02 they spread by 0.02^2 d_model, 1.6 at
Yi's width, and attention over a long prompt is nearly uniform: a decode
that read a stale cache would then give nearly the same logits); Mamba-2's
depthwise convolution U(-1/sqrt(K), 1/sqrt(K)), A = -U(1, 16) (``A_log`` =
log), dt from log-uniform [1e-3, 1e-1] stored as its inverse softplus
(``dt_bias``), D = 1.
"""

from __future__ import annotations

import zlib
from functools import partial
from typing import Dict, Mapping, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import flops

QK_SPREAD = 4.0  # standard deviation of the attention scores q.k / sqrt(d)


def root_key(seed: int) -> jax.Array:
    """A threefry key from a seed of any size (seeds may exceed 32 bits)."""
    words = np.random.SeedSequence(seed).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


def _leaf_spec(m: Mapping) -> Dict[Tuple[str, ...], tuple]:
    """path -> (shape of one layer or of the whole leaf, law, stacked)."""
    d, vp = m["d_model"], flops.padded_vocab(m)
    spec: Dict[Tuple[str, ...], tuple] = {
        ("embed",): ((vp, d), ("normal", 0.02), False),
        ("final_norm",): ((d,), ("scale",), False),
    }
    if not m.get("tie_embeddings", False):
        spec[("lm_head",)] = ((d, vp), ("normal", 0.02), False)
    if m["family"] == "dense":
        hq, hkv = m["n_heads"] * m["d_head"], m["n_kv_heads"] * m["d_head"]
        ff = m["d_ff"]
        qk = ("normal", (QK_SPREAD / d) ** 0.5)
        spec.update({
            ("layers", "attn", "wq"): ((d, hq), qk, True),
            ("layers", "attn", "wk"): ((d, hkv), qk, True),
            ("layers", "attn", "wv"): ((d, hkv), ("normal", 0.02), True),
            ("layers", "attn", "wo"): ((hq, d), ("normal", 0.02), True),
            ("layers", "mlp", "wi"): ((d, 2 * ff), ("normal", 0.02), True),
            ("layers", "mlp", "wo"): ((ff, d), ("normal", 0.02), True),
            ("layers", "norm1"): ((d,), ("scale",), True),
            ("layers", "norm2"): ((d,), ("scale",), True),
        })
    elif m["family"] == "ssm":
        di = m.get("ssm_expand", 2) * d
        n, p = m["ssm_state"], m.get("ssm_head_dim", 64)
        nh, k = di // p, m.get("ssm_conv", 4)
        spec.update({
            ("layers", "mamba", "in_proj"):
                ((d, 2 * di + 2 * n + nh), ("normal", 0.02), True),
            ("layers", "mamba", "conv_w"):
                ((k, di + 2 * n), ("uniform", k ** -0.5), True),
            ("layers", "mamba", "A_log"): ((nh,), ("a_log",), True),
            ("layers", "mamba", "D"): ((nh,), ("ones",), True),
            ("layers", "mamba", "dt_bias"): ((nh,), ("dt_bias",), True),
            ("layers", "mamba", "ssm_norm"): ((di,), ("scale",), True),
            ("layers", "mamba", "out_proj"): ((di, d), ("normal", 0.02), True),
            ("layers", "norm1"): ((d,), ("scale",), True),
        })
    else:
        raise ValueError(f"no weights for family {m['family']!r}")
    return spec


def _draw(key, shape, law):
    kind = law[0]
    if kind == "normal":
        return jax.random.normal(key, shape, jnp.float32) * law[1]
    if kind == "scale":
        return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    if kind == "uniform":
        return jax.random.uniform(key, shape, jnp.float32, -law[1], law[1])
    if kind == "a_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0, 16.0))
    if kind == "dt_bias":
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        np.log(1e-3), np.log(1e-1)))
        return dt + jnp.log(-jnp.expm1(-dt))  # inverse softplus
    raise ValueError(kind)


def _leaf_key(root, path, layer):
    tag = zlib.crc32("/".join(path).encode()) & 0x7FFFFFFF
    return jax.random.fold_in(jax.random.fold_in(root, tag), layer)


def _nest(flat: Dict[Tuple[str, ...], jax.Array]) -> dict:
    tree: dict = {}
    for path, value in flat.items():
        node = tree
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = value
    return tree


def make_params(m: Mapping, root, dtype) -> dict:
    """The whole parameter tree, stacked layers first, as ``dtype``.  Call
    under ``jax.jit`` (``params_fn``) so it is one program on the device."""
    flat = {}
    for path, (shape, law, stacked) in _leaf_spec(m).items():
        if stacked:
            layers = jnp.arange(m["n_layers"])
            leaf = jax.vmap(lambda l, p=path, s=shape, w=law: _draw(
                _leaf_key(root, p, l), s, w))(layers)
        else:
            leaf = _draw(_leaf_key(root, path, 0), shape, law)
        flat[path] = leaf.astype(dtype)
    return _nest(flat)


def params_fn(m: Mapping, dtype, out_shardings=None):
    """``root key -> parameter tree``, jitted (placed by ``out_shardings``)."""
    return jax.jit(partial(make_params, m, dtype=dtype),
                   out_shardings=out_shardings)


@partial(jax.jit, static_argnums=(0, 2, 4))
def _one_leaf(m_items, root, path, layer, dtype):
    m = dict(m_items)
    shape, law, _ = _leaf_spec(m)[path]
    return _draw(_leaf_key(root, path, layer), shape, law).astype(dtype)


def frozen(m: Mapping):
    """The scalar entries of a configuration, hashable (a jit static)."""
    return tuple(sorted((k, v) for k, v in m.items()
                        if isinstance(v, (int, float, str, bool))))


def layer_params(m: Mapping, root, layer: int, dtype) -> dict:
    """Layer ``layer``'s slice of the stacked leaves, drawn alone."""
    items = frozen(m)
    flat = {path[1:]: _one_leaf(items, root, path, layer, dtype)
            for path, (_, _, stacked) in _leaf_spec(m).items() if stacked}
    return _nest(flat)


def top_params(m: Mapping, root, dtype) -> dict:
    """The leaves outside the stack (embedding, final norm, head)."""
    items = frozen(m)
    return {path[0]: _one_leaf(items, root, path, 0, dtype)
            for path, (_, _, stacked) in _leaf_spec(m).items() if not stacked}
