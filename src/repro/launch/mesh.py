"""Production mesh construction (task §MULTI-POD DRY-RUN).

``make_production_mesh`` is a function (never module-level state) so
importing this module never touches jax device initialization.
"""

from __future__ import annotations

from typing import Optional, Tuple

import jax

from repro.distributed import MeshContext

# Every mesh axis is Auto: the model places activations with
# ``with_sharding_constraint`` (``repro.distributed.constrain``), which only
# Auto axes accept; ``jax.make_mesh`` would otherwise make them Explicit.
AUTO = jax.sharding.AxisType.Auto


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, (AUTO,) * len(axes))


def make_mesh_context(*, multi_pod: bool = False) -> MeshContext:
    mesh = make_production_mesh(multi_pod=multi_pod)
    data_axes = ("pod", "data") if multi_pod else ("data",)
    return MeshContext(mesh=mesh, data_axes=data_axes, model_axis="model")


def make_elastic_mesh_context(n_devices: Optional[int] = None,
                              model_parallel: Optional[int] = None) -> MeshContext:
    """Best mesh for an arbitrary device count (elastic re-mesh).

    Picks the largest model-parallel degree that divides the device count
    (capped at 16, the single-pod ICI domain), remaining devices become data
    parallel — the policy ``repro.launch.elastic`` applies after a resize.
    Falls back to an AbstractMesh when planning for a device count the
    current runtime does not have (pure capacity planning).
    """
    n = n_devices or len(jax.devices())
    if model_parallel is None:
        model_parallel = 1
        for cand in (16, 8, 4, 2):
            if n % cand == 0:
                model_parallel = cand
                break
    data = n // model_parallel
    if n <= len(jax.devices()):
        mesh = jax.make_mesh((data, model_parallel), ("data", "model"),
                             (AUTO, AUTO))
    else:
        mesh = jax.sharding.AbstractMesh((data, model_parallel),
                                         ("data", "model"), (AUTO, AUTO))
    return MeshContext(mesh=mesh, data_axes=("data",), model_axis="model")
