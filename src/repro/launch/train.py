"""End-to-end training driver: ``python -m repro.launch.train --arch <id>``.

Wires every substrate layer together: config registry, mesh, sharded train
state, deterministic data pipeline, the train step compiled ahead of time
(the program ``jit_train_step``), and async checkpointing with restore from
the latest checkpoint.  It trains the tiny variants by default (a CPU-sized
run); ``--no-tiny`` trains the published widths on whatever devices JAX
finds.
"""

from __future__ import annotations

import argparse
import time
from typing import Any, List, NamedTuple

import jax

from repro.checkpoint import AsyncCheckpointer, latest_checkpoint, restore_checkpoint
from repro.configs import RunConfig, get_config, list_archs, tiny_variant
from repro.configs.base import ShapeConfig
from repro.data import DataPipeline
from repro.distributed import MeshContext, set_mesh_context
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_elastic_mesh_context
from repro.launch.specs import batch_shardings, input_specs
from repro.train import init_train_state, make_train_step
from repro.train.state import abstract_train_state, state_shardings


class TrainRun(NamedTuple):
    state: Any
    metrics: List[dict]  # one record per logged step
    compiled_step: Any  # the jax.stages.Compiled every step ran


def jit_train_step(cfg, run: RunConfig, mesh_ctx: MeshContext,
                   global_batch: int, seq_len: int):
    """The jitted train step, with the state and batch shardings it takes
    and gives back: ``(step_fn, state_shardings, batch_shardings)``."""
    shardings = state_shardings(abstract_train_state(cfg), mesh_ctx, run)
    data_shardings = batch_shardings(
        input_specs(cfg, ShapeConfig("train", seq_len, global_batch, "train")),
        mesh_ctx)
    step_fn = jax.jit(make_train_step(cfg, run),
                      in_shardings=(shardings, data_shardings),
                      out_shardings=(shardings, None), donate_argnums=(0,))
    return step_fn, shardings, data_shardings


def train_loop(cfg, run: RunConfig, *, steps: int, global_batch: int,
               seq_len: int, ckpt_dir=None, seed: int = 0,
               mesh_ctx: MeshContext = None, checkpoint_every: int = 0,
               log_every: int = 10, restore: bool = True) -> TrainRun:
    """Train ``steps`` steps on the mesh; state and batches are placed by
    the sharding rules (``state_shardings``, ``batch_shardings``), so a
    multi-device mesh spreads them instead of filling device 0.

    The step is compiled ahead of time on the first batch, so every logged
    step time excludes compilation."""
    if mesh_ctx is None:
        mesh_ctx = make_elastic_mesh_context()
    set_mesh_context(mesh_ctx)
    try:
        step_fn, shardings, data_shardings = jit_train_step(
            cfg, run, mesh_ctx, global_batch, seq_len)
        state = jax.jit(init_train_state, static_argnums=0,
                        out_shardings=shardings)(cfg, jax.random.PRNGKey(seed))
        start_step = 0
        ckpt = AsyncCheckpointer(ckpt_dir) if ckpt_dir else None
        if ckpt_dir and restore:
            path = latest_checkpoint(ckpt_dir)
            if path is not None:
                state, start_step = restore_checkpoint(path, state, shardings)
                print(f"restored checkpoint @ step {start_step}")

        pipeline = DataPipeline(cfg, global_batch, seq_len, seed=seed,
                                start_step=start_step, shardings=data_shardings)
        compiled = None
        metrics_out = []
        t_wall = time.time()
        for step in range(start_step, start_step + steps):
            batch = next(pipeline)
            if compiled is None:
                t0 = time.time()
                compiled = step_fn.lower(state, batch).compile()
                print(f"compiled train step in {time.time() - t0:.1f}s")
            t0 = time.time()
            state, metrics = compiled(state, batch)
            jax.block_until_ready(metrics["loss"])
            dt = time.time() - t0
            if (step + 1) % log_every == 0 or step == start_step:
                loss = float(metrics["loss"])
                gnorm = float(metrics["grad_norm"])
                toks = global_batch * seq_len / dt
                print(f"step {step + 1:5d}  loss {loss:8.4f}  "
                      f"gnorm {gnorm:7.3f}  "
                      f"{toks:,.0f} tok/s  {dt * 1e3:.0f} ms/step")
                metrics_out.append({"step": step + 1, "loss": loss,
                                    "grad_norm": gnorm, "step_s": dt,
                                    "tokens_per_s": toks})
            if ckpt and checkpoint_every and (step + 1) % checkpoint_every == 0:
                ckpt.save(step + 1, state)
        if ckpt:
            ckpt.save(start_step + steps, state)
            ckpt.wait()
        pipeline.close()
        wall = time.time() - t_wall
        print(f"done: {steps} steps in {wall:.1f}s "
              f"({steps * global_batch * seq_len / wall:,.0f} tok/s sustained)")
        return TrainRun(state, metrics_out, compiled)
    finally:
        set_mesh_context(None)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b", choices=list_archs())
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--tiny", action="store_true", default=True)
    ap.add_argument("--no-tiny", dest="tiny", action="store_false")
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--checkpoint-every", type=int, default=100)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.tiny:
        cfg = tiny_variant(cfg)
    run = RunConfig(attention_impl="chunked", attention_chunk=64,
                    remat="full", zero=False, warmup_steps=20,
                    total_steps=args.steps)
    train_loop(cfg, run, steps=args.steps, global_batch=args.global_batch,
               seq_len=args.seq_len, ckpt_dir=args.ckpt_dir,
               checkpoint_every=args.checkpoint_every, seed=args.seed)


if __name__ == "__main__":
    main()
