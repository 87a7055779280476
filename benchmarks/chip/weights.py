"""Weights from the seed, in the program's parameter layout.

Every leaf is drawn from its own key, ``fold_in(fold_in(root, leaf), copy)``,
so one layer's slice can be drawn again alone: the program gets the whole
tree from one jitted call (``make_params``), and a reference draws layer by
layer (``layer_params``) the same numbers without holding the whole model
and without taking anything from the program.

The embedding, the final norm and the untied head are every family's and
are kept here; the other leaves are the family's (``leaves`` in its family
file, ``reference/<family>.py``), each with its shape, its law and how many
copies are stacked.  Distributions follow the published initialisations
where they matter to the numerics: matrices N(0, 0.02^2); RMSNorm scales
1 + N(0, 0.1^2), so that a scale applied wrongly shows; the family files
give the rest.
"""

from __future__ import annotations

import zlib
from functools import partial
from typing import Dict, Mapping, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import flops, harness


def root_key(seed: int) -> jax.Array:
    """A threefry key from a seed of any size (seeds may exceed 32 bits)."""
    words = np.random.SeedSequence(seed).generate_state(2, np.uint32)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32),
                                    impl="threefry2x32")


class Leaf(NamedTuple):
    """A family's parameter leaf: the shape of one copy, its law (one of
    ``_draw``'s, or a function ``(key, shape) -> float32 array``), and how
    many copies are stacked on a leading axis (None: one a layer,
    ``n_layers``; 0: one leaf, not stacked)."""
    shape: tuple
    law: object
    copies: Optional[int] = None


def _leaf_spec(m: Mapping) -> Dict[Tuple[str, ...], tuple]:
    """path -> (shape of one copy, law, copies stacked; 0: not stacked)."""
    d, vp = m["d_model"], flops.padded_vocab(m)
    spec: Dict[Tuple[str, ...], tuple] = {
        ("embed",): ((vp, d), ("normal", 0.02), 0),
        ("final_norm",): ((d,), ("scale",), 0),
    }
    if not m.get("tie_embeddings", False):
        spec[("lm_head",)] = ((d, vp), ("normal", 0.02), 0)
    for path, leaf in harness.family(m).leaves(m).items():
        copies = m["n_layers"] if leaf.copies is None else leaf.copies
        spec[path] = (leaf.shape, leaf.law, copies)
    return spec


def _draw(key, shape, law):
    if callable(law):
        return law(key, shape)
    kind = law[0]
    if kind == "normal":
        return jax.random.normal(key, shape, jnp.float32) * law[1]
    if kind == "scale":
        return 1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)
    if kind == "ones":
        return jnp.ones(shape, jnp.float32)
    if kind == "uniform":
        return jax.random.uniform(key, shape, jnp.float32, -law[1], law[1])
    raise ValueError(kind)


def _leaf_key(root, path, copy):
    tag = zlib.crc32("/".join(path).encode()) & 0x7FFFFFFF
    return jax.random.fold_in(jax.random.fold_in(root, tag), copy)


def _nest(flat: Dict[Tuple[str, ...], jax.Array]) -> dict:
    tree: dict = {}
    for path, value in flat.items():
        node = tree
        for part in path[:-1]:
            node = node.setdefault(part, {})
        node[path[-1]] = value
    return tree


def make_params(m: Mapping, root, dtype) -> dict:
    """The whole parameter tree, stacked copies first, as ``dtype``.  Call
    under ``jax.jit`` (``params_fn``) so it is one program on the device."""
    flat = {}
    for path, (shape, law, copies) in _leaf_spec(m).items():
        if copies:
            leaf = jax.vmap(lambda c, p=path, s=shape, w=law: _draw(
                _leaf_key(root, p, c), s, w))(jnp.arange(copies))
        else:
            leaf = _draw(_leaf_key(root, path, 0), shape, law)
        flat[path] = leaf.astype(dtype)
    return _nest(flat)


def params_fn(m: Mapping, dtype, out_shardings=None):
    """``root key -> parameter tree``, jitted (placed by ``out_shardings``)."""
    return jax.jit(partial(make_params, m, dtype=dtype),
                   out_shardings=out_shardings)


@partial(jax.jit, static_argnums=(0, 2, 4))
def _one_leaf(m_items, root, path, copy, dtype):
    m = dict(m_items)
    shape, law, _ = _leaf_spec(m)[path]
    return _draw(_leaf_key(root, path, copy), shape, law).astype(dtype)


def frozen(m: Mapping):
    """The scalar entries of a configuration, hashable (a jit static)."""
    return tuple(sorted((k, v) for k, v in m.items()
                        if isinstance(v, (int, float, str, bool))))


def layer_params(m: Mapping, root, layer: int, dtype,
                 stack: str = "layers") -> dict:
    """Copy ``layer`` of the leaves stacked under ``stack``, drawn alone."""
    items = frozen(m)
    flat = {path[1:]: _one_leaf(items, root, path, layer, dtype)
            for path, (_, _, copies) in _leaf_spec(m).items()
            if copies and path[0] == stack}
    return _nest(flat)


def top_params(m: Mapping, root, dtype) -> dict:
    """The leaves that are not stacked (embedding, final norm, head, and any
    of the family's)."""
    items = frozen(m)
    return _nest({path: _one_leaf(items, root, path, 0, dtype)
                  for path, (_, _, copies) in _leaf_spec(m).items()
                  if not copies})
