"""Training through the program's compiled step, fed by its ``DataPipeline``.

Set-up builds one object, the train step compiled ahead of time
(``jit_train_step``) with its state (the seed's float32 parameters, the
program's AdamW state), and drives it through its first ``check_steps``
steps on the pipeline's batches: the loss of each step, the norm of each
leaf of the first gradient as the optimizer got it (its first moment after
one step over 1 - beta1) and of each leaf's change over those steps are
kept.  The same object then runs the window: the next batch, the step, and
the wait for its loss, until ``--seconds`` have passed.

End to end: tokens of every step completed in the window over the window's
time (all chips together).

Correct: once the window has closed and the program's state is freed, the
float32 reference (``reference/train.py``, with the ``loss`` of the model's
family file) takes the same steps from the same parameters on the same
batches (rebuilt by the benchmark's own copy of the token stream), and the
three numbers of ``reference.train.compare`` are held to the traffic file's
limits.
"""

from __future__ import annotations

import gc
import math
import time
from functools import partial

import jax
import jax.numpy as jnp
from jax.profiler import TraceAnnotation

from benchmarks.chip import flops, harness, trace, traffic, weights
from benchmarks.chip.reference import train as ref_train


def build(ctx):
    """The compiled step, its state and the data pipeline."""
    from repro.configs.base import ModelConfig, RunConfig
    from repro.data import DataPipeline
    from repro.distributed import set_mesh_context
    from repro.launch.mesh import make_elastic_mesh_context
    from repro.launch.train import jit_train_step
    from repro.optim import adamw_init
    from repro.train.state import TrainState

    t, m = ctx.traffic, ctx.model
    cfg, run = ModelConfig(**m), RunConfig(**ctx.cell.config["run"])
    mesh_ctx = make_elastic_mesh_context(
        len(ctx.devices), model_parallel=t.get("model_parallel", 1))
    set_mesh_context(mesh_ctx)
    step_fn, shardings, data_shardings = jit_train_step(
        cfg, run, mesh_ctx, t["batch"], t["seq"])
    params_fn = weights.params_fn(m, jnp.float32,
                                  out_shardings=shardings.params)
    root = weights.root_key(ctx.seed)
    state = jax.jit(
        lambda p: TrainState(params=p, opt=adamw_init(p),
                             step=jnp.zeros((), jnp.int32)),
        out_shardings=shardings, donate_argnums=0)(params_fn(root))
    pipeline = DataPipeline(cfg, t["batch"], t["seq"], seed=ctx.seed,
                            shardings=data_shardings)
    return root, params_fn, step_fn, state, pipeline


def first_steps(t, root, params_fn, step_fn, state, pipeline):
    """Compile the step on the first batch and take ``check_steps`` steps:
    the compiled step, the state after them, and what they read."""
    first = next(pipeline)
    compiled = step_fn.lower(state, first).compile()
    prog = {"loss": []}
    for i in range(t["check_steps"]):
        batch = first if i == 0 else next(pipeline)
        state, metrics = compiled(state, batch)
        prog["loss"].append(float(metrics["loss"]))
        if i == 0:
            prog["grad"] = {k: v / (1 - ref_train.B1) for k, v in
                            ref_train.leaf_norms(state.opt.mu).items()}
    params0 = params_fn(root)
    prog["delta"] = ref_train.leaf_norms(jax.tree.map(
        lambda p, p0: p.astype(jnp.float32) - p0, state.params, params0))
    return compiled, state, prog


def step_once(compiled, state, pipeline):
    with TraceAnnotation("data.next"):
        batch = next(pipeline)
    with TraceAnnotation("step"):
        state, metrics = compiled(state, batch)
        loss = float(metrics["loss"])
    return state, loss


def run(ctx):
    from repro.distributed import set_mesh_context

    t, m = ctx.traffic, ctx.model
    root, params_fn, step_fn, state, pipeline = build(ctx)
    try:
        compiled, state, prog = first_steps(t, root, params_fn, step_fn,
                                            state, pipeline)
        t_first = time.perf_counter()

        losses, reduced = [], None
        if ctx.trace:
            captured = []
            try:
                with trace.capture() as captured:
                    for _ in range(t["trace_steps"]):
                        state, loss = step_once(compiled, state, pipeline)
                        losses.append(loss)
                reduced = trace.reduce(trace.load(
                    captured[-1], spans=("data.next", "step")))
            finally:
                trace.discard(captured)
        else:
            while time.perf_counter() - t_first < ctx.seconds:
                state, loss = step_once(compiled, state, pipeline)
                losses.append(loss)
        window = time.perf_counter() - t_first
        peak = harness.memory_peak(ctx.devices)
    finally:
        pipeline.close()
        set_mesh_context(None)

    tokens_per_step = t["batch"] * t["seq"]
    done = [x for x in losses if math.isfinite(x)]
    values = {"train_tokens_per_s": len(done) * tokens_per_step / window}
    counters = {"steps": len(done), "tokens": len(done) * tokens_per_step,
                "flops_per_token": flops.train_flops_per_token(m, t["seq"])}

    jax.tree.map(lambda x: x.delete(), state)
    del state, compiled
    gc.collect()
    t_ref = time.perf_counter()
    numbers = ref_train.compare(prog, reference_run(ctx, root))
    checks = {k: (numbers[k], t["limits"][k]) for k in ("loss", "grad",
                                                          "delta")}
    return harness.Outcome(attempted=len(losses),
                           failed=len(losses) - len(done),
                           setup_s=t_first - ctx.t_start, checks=checks,
                           values=values, reduced=reduced, counters=counters,
                           memory_peak_bytes=peak,
                           reference_s=time.perf_counter() - t_ref)


def reference_batches(ctx):
    t, m = ctx.traffic, ctx.model
    return [traffic.train_batch(m["vocab"], t["batch"], t["seq"], ctx.seed, i)
            for i in range(t["check_steps"])]


def reference_run(ctx, root, precision="f32", half_batch=False):
    """The reference's steps from the seed: loss, first gradient and change
    of each leaf (``reference.train.follow``)."""
    m = ctx.model
    loss_fn = partial(harness.family(m).loss, m=m, precision=precision)
    params0 = weights.params_fn(m, jnp.float32)(root)
    return ref_train.follow(params0, reference_batches(ctx),
                            lambda p, x, y: loss_fn(p, x, y),
                            ctx.cell.config["run"], precision=precision,
                            half_batch=half_batch)
