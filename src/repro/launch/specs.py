"""ShapeDtypeStruct input specs + sharding specs for every (arch × shape)
cell — the shannon/kernels pattern: weak-type-correct, shardable, zero
allocation."""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ModelConfig, RunConfig, ShapeConfig
from repro.distributed import MeshContext
from repro.distributed.sharding import _sanitize
from repro.models import init_cache


def sds(shape, dtype) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(tuple(shape), jnp.dtype(dtype))


def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> Dict[str, Any]:
    """Abstract model inputs for one cell.

    train  : {tokens, labels[, frontend]}
    prefill: {tokens[, frontend]}
    decode : {cache, tokens}
    """
    b, s = shape.global_batch, shape.seq_len
    dt = jnp.dtype(cfg.dtype)

    if shape.kind == "decode":
        cache = jax.eval_shape(lambda: init_cache(cfg, b, s))
        return {"cache": cache, "tokens": sds((b, 1), jnp.int32)}

    specs: Dict[str, Any] = {}
    if cfg.frontend == "vision_stub":
        f = cfg.frontend_len
        specs["tokens"] = sds((b, s - f), jnp.int32)
        specs["frontend"] = sds((b, f, cfg.d_model), dt)
        if shape.kind == "train":
            specs["labels"] = sds((b, s - f), jnp.int32)
    elif cfg.frontend == "audio_stub":
        specs["tokens"] = sds((b, s), jnp.int32)
        specs["frontend"] = sds((b, cfg.frontend_len, cfg.d_model), dt)
        if shape.kind == "train":
            specs["labels"] = sds((b, s), jnp.int32)
    else:
        specs["tokens"] = sds((b, s), jnp.int32)
        if shape.kind == "train":
            specs["labels"] = sds((b, s), jnp.int32)
    return specs


def batch_shardings(specs: Dict[str, Any], ctx: MeshContext) -> Dict[str, Any]:
    data = tuple(ctx.data_axes)
    data = data if len(data) > 1 else data[0]

    def shard(leaf):
        spec = P(data, *([None] * (len(leaf.shape) - 1)))
        return NamedSharding(ctx.mesh, _sanitize(ctx, leaf.shape, spec))

    return {k: jax.tree.map(shard, v) if k != "cache" else v
            for k, v in specs.items()}


def cache_shardings(cache_specs, ctx: MeshContext):
    """KV caches: batch over data, sequence over model (flash-decode split-K
    falls out of GSPMD).  SSM states: batch over data, heads over model."""
    data = tuple(ctx.data_axes)
    data = data if len(data) > 1 else data[0]

    def leaf_spec(path, leaf):
        name = ""
        for p in path:
            if hasattr(p, "key"):
                name = p.key
        nd = len(leaf.shape)
        if name in ("k", "v", "dk", "dv", "cross_k", "cross_v") and nd == 5:
            spec = P(None, data, "model", None, None)
        elif name == "ssm":  # (L, B, H, N, P)
            spec = P(None, data, "model", None, None)
        elif name == "conv":  # (L, B, K - 1, C)
            spec = P(None, data, None, "model")
        else:  # pos and misc scalars
            spec = P()
        return NamedSharding(ctx.mesh, _sanitize(ctx, leaf.shape, spec))

    return jax.tree_util.tree_map_with_path(leaf_spec, cache_specs)


def model_flops_estimate(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """MODEL_FLOPS per step: 6·N·D train (N = active params), 2·N·D forward."""
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n_active * shape.tokens
    if shape.kind == "prefill":
        return 2.0 * n_active * shape.tokens
    return 2.0 * n_active * shape.global_batch  # decode: one token per seq
