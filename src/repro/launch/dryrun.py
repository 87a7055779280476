"""Multi-pod dry-run: ``.lower().compile()`` every (architecture × input
shape × mesh) cell on placeholder devices and record memory / cost /
roofline artifacts (task §MULTI-POD DRY-RUN).

Run as a script, it asks the CPU backend for 512 placeholder devices before
anything initializes a backend; importing the module changes nothing.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch tinyllama-1.1b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out artifacts/]
"""

import os

if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402
from pathlib import Path  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import SHAPES, get_config, list_archs  # noqa: E402
from repro.configs.base import ModelConfig, RunConfig, ShapeConfig  # noqa: E402
from repro.core.hlo import roofline_from_compiled  # noqa: E402
from repro.distributed import set_mesh_context  # noqa: E402
from repro.launch.mesh import make_mesh_context  # noqa: E402
from repro.launch.specs import (  # noqa: E402
    batch_shardings, cache_shardings, input_specs, model_flops_estimate,
)
from repro.models import decode_step, prefill  # noqa: E402
from repro.train import make_train_step  # noqa: E402
from repro.train.state import abstract_train_state, state_shardings  # noqa: E402


def cell_skip_reason(cfg: ModelConfig, shape: ShapeConfig) -> str:
    """Documented skips (DESIGN.md §5): '' means the cell runs."""
    if shape.name == "long_500k" and not cfg.supports_long_context:
        return ("full-attention architecture at 500k context: O(S^2) attention "
                "and a 500k dense KV cache are out of scope by design "
                "(sub-quadratic archs run this cell)")
    return ""


def default_run_config(cfg: ModelConfig, shape: ShapeConfig,
                       overrides=None) -> RunConfig:
    kw = dict(
        attention_impl="chunked",
        attention_chunk=512,
        remat="full" if shape.kind == "train" else "none",
        seq_shard=shape.kind == "train",
        zero=shape.kind == "train",
        fsdp=shape.kind == "train",
        loss_chunk=0,
    )
    kw.update(overrides or {})
    return RunConfig(**kw)


def lower_cell(arch: str, shape_name: str, multi_pod: bool = False,
               run_overrides=None):
    """Build the jitted step for one cell and return (lowered, meta)."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    reason = cell_skip_reason(cfg, shape)
    if reason:
        return None, {"arch": arch, "shape": shape_name, "skipped": reason}

    ctx = make_mesh_context(multi_pod=multi_pod)
    set_mesh_context(ctx)
    run = default_run_config(cfg, shape, run_overrides)
    specs = input_specs(cfg, shape)
    scalar = NamedSharding(ctx.mesh, P())

    try:
        if shape.kind == "train":
            state = abstract_train_state(cfg)
            st_shard = state_shardings(state, ctx, run)
            bshard = batch_shardings(specs, ctx)
            step = make_train_step(cfg, run)
            jitted = jax.jit(
                step,
                in_shardings=(st_shard, bshard),
                out_shardings=(st_shard, None),
                donate_argnums=(0,),
            )
            lowered = jitted.lower(state, specs)
        elif shape.kind == "prefill":
            state = abstract_train_state(cfg)
            p_shard = state_shardings(state, ctx, run).params
            bshard = batch_shardings(specs, ctx)

            def prefill_step(params, tokens, frontend=None):
                return prefill(params, cfg, run, tokens, frontend=frontend)

            if "frontend" in specs:
                cache_spec = jax.eval_shape(prefill_step, state.params,
                                            specs["tokens"], specs["frontend"])
            else:
                cache_spec = jax.eval_shape(prefill_step, state.params,
                                            specs["tokens"])
            out_cache_shard = cache_shardings(cache_spec[1], ctx)
            jitted = jax.jit(
                prefill_step,
                in_shardings=(p_shard,) + tuple(
                    bshard[k] for k in ("tokens", "frontend") if k in bshard),
                out_shardings=(None, out_cache_shard),
            )
            args = [state.params, specs["tokens"]]
            if "frontend" in specs:
                args.append(specs["frontend"])
            lowered = jitted.lower(*args)
        else:  # decode
            state = abstract_train_state(cfg)
            p_shard = state_shardings(state, ctx, run).params
            c_shard = cache_shardings(specs["cache"], ctx)
            tok_shard = batch_shardings(
                {"tokens": specs["tokens"]}, ctx)["tokens"]

            def serve_step(params, cache, tokens):
                return decode_step(params, cfg, run, cache, tokens)

            jitted = jax.jit(
                serve_step,
                in_shardings=(p_shard, c_shard, tok_shard),
                out_shardings=(None, c_shard),
                donate_argnums=(1,),
            )
            lowered = jitted.lower(state.params, specs["cache"], specs["tokens"])
        meta = {
            "arch": arch, "shape": shape_name,
            "mesh": "2x16x16" if multi_pod else "16x16",
            "kind": shape.kind,
            "model_flops": model_flops_estimate(cfg, shape),
        }
        return lowered, meta
    finally:
        set_mesh_context(None)


def run_cell(arch: str, shape_name: str, multi_pod: bool = False,
             out_dir=None, run_overrides=None, save_hlo: bool = False,
             name_suffix: str = ""):
    t0 = time.time()
    lowered, meta = lower_cell(arch, shape_name, multi_pod, run_overrides)
    if lowered is None:
        print(f"SKIP  {arch} x {shape_name}: {meta['skipped']}")
        return meta
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    ma = compiled.memory_analysis()
    hlo_text = compiled.as_text()
    report = roofline_from_compiled(
        compiled, name=f"{arch}/{shape_name}{name_suffix}",
        model_flops=meta["model_flops"], hlo_text=hlo_text)
    from repro.core.hlo.hotspots import cpu_bf16_artifact_bytes
    artifact = cpu_bf16_artifact_bytes(hlo_text)
    row = report.row()
    row["cpu_convert_artifact_bytes"] = artifact
    row.update(meta)
    row.update({
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "arg_bytes": int(ma.argument_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "out_bytes": int(ma.output_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
    })
    mem = row["arg_bytes"] + row["temp_bytes"]
    mem_adj = max(mem - artifact, row["arg_bytes"])
    row["mem_per_device_adjusted"] = mem_adj
    print(f"OK    {arch} x {shape_name} [{row['mesh']}] "
          f"mem/dev={mem / 2**30:.2f}GiB "
          f"(tpu-adj {mem_adj / 2**30:.2f}GiB) "
          f"dominant={row['dominant']} bound={row['bound_s'] * 1e3:.2f}ms "
          f"(lower {t_lower:.0f}s compile {t_compile:.0f}s)")
    print(report.render())

    if out_dir is not None:
        out_dir = Path(out_dir)
        out_dir.mkdir(parents=True, exist_ok=True)
        stem = f"{arch}__{shape_name}__{row['mesh'].replace('x', '-')}{name_suffix}"
        (out_dir / f"{stem}.json").write_text(json.dumps(row, indent=2, default=str))
        if save_hlo:
            (out_dir / f"{stem}.hlo.txt").write_text(compiled.as_text())
    return row


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="artifacts/dryrun")
    ap.add_argument("--save-hlo", action="store_true")
    args = ap.parse_args()

    cells = []
    archs = list_archs() if (args.all or args.arch is None) else [args.arch]
    shapes = list(SHAPES) if (args.all or args.shape is None) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                cells.append((arch, shape, mp))

    failures = []
    for arch, shape, mp in cells:
        try:
            run_cell(arch, shape, mp, out_dir=args.out, save_hlo=args.save_hlo)
        except Exception as e:  # noqa: BLE001
            failures.append((arch, shape, mp, repr(e)))
            print(f"FAIL  {arch} x {shape} multi_pod={mp}: {e}")
            traceback.print_exc()
    print(f"\n{len(cells) - len(failures)}/{len(cells)} cells OK")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
