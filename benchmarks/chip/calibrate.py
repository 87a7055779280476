"""Readings that a cell's limits are set from: the program's, its control's
and its faults', at the cell's own size on the chip, over many seeds in one
process.  The benchmark's own runs never run this.

    python3 benchmarks/chip/calibrate.py --workload <cell> --seeds 1,2,3 \
        [--out <file.jsonl>]

For each seed it prints one JSON line:

- ``serve_offline``: ``gap`` of the program (one job served by
  ``ServeEngine.generate``, the traffic's sample of requests held to the
  float32 reference), of the control (the reference computed in float8,
  e4m3 scaled per tensor: the gap of the token it puts first at each of the
  same positions), and of the fault "a decode step that returns its cache
  unchanged" planted in the engine (``stale_cache``; the same job served
  again).
- ``train``: ``loss``, ``grad``, ``delta`` (``reference.train.compare``)
  of the program's first steps, of the control (the reference computed in
  bfloat16, parameters held in bfloat16), and of the fault "half of the
  batch left out, the mean over the rest" planted in the reference.  The
  fault "a step that returns its state unchanged" reads ``delta`` = 1 by
  construction and needs no run.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path[:] = [p for p in sys.path if Path(p or ".").resolve() != HERE]
for extra in (ROOT / "src", ROOT):
    if str(extra) not in sys.path:
        sys.path.insert(0, str(extra))

from benchmarks.chip import harness, traffic, weights  # noqa: E402


def stale_cache(decode):
    """``decode`` with the fault "a decode step that returns its cache
    unchanged": it hands back a copy of the cache taken before the step,
    so the fault holds where decode donates (and so deletes) its cache."""
    import jax
    import jax.numpy as jnp

    def stale(params, cache, tokens):
        kept = jax.tree.map(jnp.copy, cache)
        return decode(params, cache, tokens)[0], kept
    return stale


def serve_readings(ctx, seeds):
    import jax
    import jax.numpy as jnp
    from benchmarks.chip.runners import serve_offline as drv

    t, m = ctx.traffic, ctx.model
    engine = None
    for seed in seeds:
        ctx.seed = seed
        if engine is None:
            root, params, engine = drv.build(ctx)
        else:
            root = weights.root_key(seed)
            params = weights.params_fn(m, jnp.bfloat16)(root)
            engine.params = params
        prompts, results, _, _ = drv.serve(engine, t, m["vocab"], seed, 0)
        decode = engine.decode
        engine.decode = stale_cache(decode)
        _, stale, _, _ = drv.serve(engine, t, m["vocab"], seed, 0)
        engine.decode = decode
        pick = traffic.sample(seed, len(results), t["check_requests"])
        chosen = [prompts[i] for i in pick]
        served = [results[i].tokens for i in pick]
        stale = [stale[i].tokens for i in pick]
        jax.tree.map(lambda x: x.delete(), params)
        engine.params = None
        del params, results
        gc.collect()
        gaps = drv.reference_gaps(m, root, chosen, served, ("f32", "fp8"))
        stale_gap = drv.reference_gaps(m, root, chosen, stale)["f32"]
        yield {"seed": seed, "program": {"gap": float(gaps["f32"].max())},
               "control": {"gap": float(gaps["fp8"].max())},
               "stale_cache": {"gap": float(stale_gap.max())}}


def train_readings(ctx, seeds):
    import jax
    from benchmarks.chip.runners import train as drv
    from benchmarks.chip.reference import train as ref_train
    from repro.distributed import set_mesh_context

    t = ctx.traffic
    for seed in seeds:
        ctx.seed = seed
        root, params_fn, step_fn, state, pipeline = drv.build(ctx)
        try:
            compiled, state, prog = drv.first_steps(
                t, root, params_fn, step_fn, state, pipeline)
        finally:
            pipeline.close()
            set_mesh_context(None)
        jax.tree.map(lambda x: x.delete(), state)
        del state, compiled
        gc.collect()
        ref = drv.reference_run(ctx, root)
        control = drv.reference_run(ctx, root, precision="bf16")
        half = drv.reference_run(ctx, root, half_batch=True)

        def numbers(got):
            c = ref_train.compare(got, ref)
            return {k: c[k] for k in ("loss", "grad", "delta",
                                      "_grad_leaf", "_delta_leaf")}

        yield {"seed": seed, "program": numbers(prog),
               "control": numbers(control), "half_batch": numbers(half),
               "leaves_kept": ref_train.compare(prog, ref)["_leaves_kept"],
               "ref_loss": ref["loss"], "program_loss": prog["loss"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    cell = harness.load_cell(args.workload)
    try:
        devices = harness.start_jax(cell.entry["chips"])
        peak = harness.load_peaks(devices[0].device_kind)
    except harness.NoChip as exc:
        print(f"calibrate: {exc}", file=sys.stderr)
        return 2
    harness.enable_compile_cache()
    ctx = harness.Context(cell, 0, 0.0, False, devices, peak, 0.0)
    seeds = [int(s) for s in args.seeds.split(",")]
    readings = {"serve_offline": serve_readings,
                "train": train_readings}[cell.traffic["kind"]]
    sink = open(args.out, "a") if args.out else None
    try:
        for line in readings(ctx, seeds):
            text = json.dumps(line)
            print(text, flush=True)
            if sink:
                sink.write(text + "\n")
                sink.flush()
    finally:
        if sink:
            sink.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
