"""Sharding rules: logical→physical mapping with divisibility guards.

Axes convention (DESIGN.md §4.1):
  * ``data axes``  — batch / token parallelism: ``("data",)`` single-pod,
    ``("pod", "data")`` multi-pod (outer DP over pods).
  * ``model axis`` — tensor/expert parallelism: ``"model"``.

``constrain`` applies an activation sharding constraint, silently dropping
mesh axes that do not divide the corresponding dimension (e.g. 4 KV heads on
a 16-way model axis → replicated KV) and becoming a no-op when no mesh
context is installed (CPU smoke tests).
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field
from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclass
class MeshContext:
    mesh: Mesh
    data_axes: Tuple[str, ...] = ("data",)
    model_axis: str = "model"

    @property
    def data_size(self) -> int:
        return int(np.prod([self.mesh.shape[a] for a in self.data_axes]))

    @property
    def model_size(self) -> int:
        return int(self.mesh.shape[self.model_axis])


_ctx = threading.local()


def set_mesh_context(ctx: Optional[MeshContext]) -> None:
    _ctx.value = ctx


def current_mesh() -> Optional[MeshContext]:
    return getattr(_ctx, "value", None)


def _filter_axes(ctx: MeshContext, axis):
    """Keep only axes present in the mesh (('pod','data') on a single-pod
    mesh degrades to ('data',))."""
    names = set(ctx.mesh.axis_names)
    if axis is None:
        return None
    if isinstance(axis, (tuple, list)):
        kept = tuple(a for a in axis if a in names)
        if not kept:
            return None
        return kept if len(kept) > 1 else kept[0]
    return axis if axis in names else None


def _axis_size(ctx: MeshContext, axis) -> int:
    if axis is None:
        return 1
    if isinstance(axis, (tuple, list)):
        return int(np.prod([ctx.mesh.shape[a] for a in axis]))
    return int(ctx.mesh.shape[axis])


def _sanitize(ctx: MeshContext, shape: Sequence[int], spec: P) -> P:
    """Drop mesh-absent axes and spec axes that do not divide their dim."""
    entries = list(spec) + [None] * (len(shape) - len(spec))
    clean = []
    for dim, axis in zip(shape, entries):
        axis = _filter_axes(ctx, axis)
        if axis is None:
            clean.append(None)
            continue
        size = _axis_size(ctx, axis)
        clean.append(axis if size > 0 and dim % size == 0 else None)
    while clean and clean[-1] is None:
        clean.pop()
    return P(*clean)


def constrain(x, *spec_entries) -> jax.Array:
    """``with_sharding_constraint`` with divisibility guard; no-op sans mesh."""
    ctx = current_mesh()
    if ctx is None:
        return x
    spec = _sanitize(ctx, x.shape, P(*spec_entries))
    return jax.lax.with_sharding_constraint(x, NamedSharding(ctx.mesh, spec))


# ---------------------------------------------------------------------------
# Parameter sharding rules
# ---------------------------------------------------------------------------

# Rules keyed by parameter-leaf name; each value maps tensor rank -> spec
# builder (m = model axis).  Layer-stacked tensors have a leading L dim that
# stays unsharded.
def spec_for_path(path: Tuple[str, ...], shape: Tuple[int, ...],
                  model_axis: str = "model") -> P:
    name = path[-1] if path else ""
    m = model_axis
    ndim = len(shape)

    def last(axis):  # shard the last dim
        return P(*([None] * (ndim - 1) + [axis]))

    def second_last(axis):
        if ndim < 2:
            return P()
        return P(*([None] * (ndim - 2) + [axis, None]))

    if name in ("embed",):
        return P(m, None)  # (V, d) vocab-sharded
    if name in ("lm_head",):
        return last(m)  # (d, V)
    if name in ("wq", "wk", "wv", "wi", "w_gate_up", "in_proj", "cross_wk",
                "cross_wv", "cross_wq", "adapter_up"):
        return last(m)
    if name in ("wo", "out_proj", "cross_wo"):
        return second_last(m)
    if name in ("moe_wi",):  # (L, E, d, ffe): expert-parallel
        return P(None, m, None, None) if ndim == 4 else second_last(m)
    if name in ("moe_wo",):
        return P(None, m, None, None) if ndim == 4 else second_last(m)
    if name in ("router",):
        return P()
    if name in ("conv_w", "conv_b", "A_log", "D", "dt_bias"):
        return P()  # small SSM tensors: replicated
    # norms, scales, biases, positional tables: replicated
    return P()


def param_sharding_rules(params, mesh_ctx: MeshContext):
    """Pytree of NamedShardings for a parameter pytree (divisibility-guarded)."""

    def leaf_spec(path, leaf):
        names = tuple(
            p.key if hasattr(p, "key") else str(getattr(p, "idx", p))
            for p in path
        )
        spec = spec_for_path(names, leaf.shape, mesh_ctx.model_axis)
        spec = _sanitize(mesh_ctx, leaf.shape, spec)
        return NamedSharding(mesh_ctx.mesh, spec)

    return jax.tree_util.tree_map_with_path(leaf_spec, params)


def zero_extend(sharding: NamedSharding, shape: Tuple[int, ...],
                mesh_ctx: MeshContext) -> NamedSharding:
    """ZeRO/FSDP: additionally shard the first free divisible dim over the
    data axes.  No-op if the data axes are already used by the spec (a mesh
    axis may appear at most once in a PartitionSpec)."""
    spec = list(sharding.spec) + [None] * (len(shape) - len(sharding.spec))
    used = set()
    for entry in spec:
        for a in (entry if isinstance(entry, (tuple, list)) else (entry,)):
            if a is not None:
                used.add(a)
    data_axes = tuple(mesh_ctx.data_axes)
    if used & set(data_axes):
        return sharding
    size = mesh_ctx.data_size
    for i, (dim, axis) in enumerate(zip(shape, spec)):
        if axis is None and dim % size == 0 and dim >= size:
            spec[i] = data_axes if len(data_axes) > 1 else data_axes[0]
            return NamedSharding(mesh_ctx.mesh, P(*spec))
    return sharding
