"""Offline batch generation through ``ServeEngine.generate``.

One client, closed loop: it submits a job of ``batch`` prompts of
``prompt_len`` tokens for ``new_tokens`` greedy tokens each, waits for the
whole job, and submits the next, until ``--seconds`` have passed since the
first (the job under way then runs to its end and counts).  Every job has
one prompt length and one wave size, so the window runs the programs that
the warm-up job compiled and nothing else.

End to end: generated tokens of all jobs over the window's time, and the
95th percentile of every request's completion minus its submission.

Correct: once the window has closed and the program's weights are freed, a
sample of the requests (drawn from the seed; every request has the same
length) goes through the float32 reference of the model's family
(``logits`` in its family file): prompt plus served tokens, one causal
pass, and at each served position the gap by which the served token's
logit lies below the reference's best.  The widest gap is held to
the traffic file's limit.
"""

from __future__ import annotations

import gc
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from benchmarks.chip import flops, harness, trace, traffic, weights

WARM_JOB = 2**31 - 1  # a job index the window never reaches


def p95(values):
    if len(values) < 2:  # quantiles needs two; a real window has hundreds
        return max(values)
    return statistics.quantiles(values, n=20, method="inclusive")[-1]


def build(ctx):
    """Weights from the seed on the device, and the engine that serves them."""
    from repro.configs.base import ModelConfig, RunConfig
    from repro.serving import ServeEngine

    t, m = ctx.traffic, ctx.model
    root = weights.root_key(ctx.seed)
    params = weights.params_fn(m, jnp.bfloat16)(root)
    engine = ServeEngine(ModelConfig(**m), params,
                         run=RunConfig(**ctx.cell.config["run"]),
                         batch_size=t["batch"],
                         max_len=t["prompt_len"] + t["new_tokens"])
    return root, params, engine


def serve(engine, t, vocab, seed, job):
    prompts = traffic.job_prompts(t, vocab, seed, job)
    submitted = time.perf_counter()
    with TraceAnnotation("generate"):
        results = engine.generate(prompts, max_new_tokens=t["new_tokens"])
    return prompts, results, submitted, time.perf_counter()


def reference_gaps(m, root, prompts, served, precisions=("f32",)):
    """Per precision, the gap of each served token below the f32 reference's
    best logit, (R, N): at ``"f32"`` for the served tokens themselves, at a
    lower precision for the token that precision puts first."""
    p_len = len(prompts[0])
    seqs = np.asarray([p + s[:-1] for p, s in zip(prompts, served)],
                      np.int32)
    out = harness.family(m).logits(m, root, seqs, p_len - 1, jnp.bfloat16,
                                   precisions)
    ref = out["f32"]
    best = jnp.max(ref, axis=-1)
    gaps = {"f32": best - jnp.take_along_axis(
        ref, jnp.asarray(np.asarray(served, np.int32))[..., None], -1)[..., 0]}
    for p in precisions:
        if p != "f32":
            choice = jnp.argmax(out[p], axis=-1)
            gaps[p] = best - jnp.take_along_axis(ref, choice[..., None],
                                                 -1)[..., 0]
    return {k: np.asarray(v) for k, v in gaps.items()}


def run(ctx):
    t, m = ctx.traffic, ctx.model
    root, params, engine = build(ctx)
    serve(engine, t, m["vocab"], ctx.seed, WARM_JOB)  # compiles every shape
    t_first = time.perf_counter()

    jobs = []
    reduced = None
    if ctx.trace:
        captured = []
        try:
            with trace.capture() as captured:
                for j in range(t["trace_jobs"]):
                    jobs.append(serve(engine, t, m["vocab"], ctx.seed, j))
            reduced = trace.reduce(trace.load(
                captured[-1], spans=("generate",)))
        finally:
            trace.discard(captured)
    else:
        while time.perf_counter() - t_first < ctx.seconds:
            jobs.append(serve(engine, t, m["vocab"], ctx.seed, len(jobs)))
    t_end = jobs[-1][3]
    peak = harness.memory_peak(ctx.devices)

    n_new = t["new_tokens"]
    requests = [(prompts[i], r, sub, done)
                for prompts, results, sub, done in jobs
                for i, r in enumerate(results)]
    attempted = len(jobs) * t["batch"]
    finished = [q for q in requests if len(q[1].tokens) == n_new]
    failed = attempted - len(finished)
    window = t_end - t_first
    values = {
        "serve_tokens_per_s": len(finished) * n_new / window,
        "serve_request_p95_s": p95([done - sub for _, _, sub, done
                                    in finished] or [window]),
    }
    counters = {"jobs": len(jobs), "requests": len(finished),
                "tokens": len(finished) * n_new,
                "decode_calls": len(jobs) * (n_new - 1),
                "job_flops": flops.serve_job_flops(
                    m, t["batch"], t["prompt_len"], n_new)}

    pick = traffic.sample(ctx.seed, len(finished), t["check_requests"])
    prompts = [finished[i][0] for i in pick]
    served = [finished[i][1].tokens for i in pick]
    jax.tree.map(lambda x: x.delete(), params)
    del engine, params, jobs, requests, finished
    gc.collect()
    t_ref = time.perf_counter()
    gap = float(reference_gaps(m, root, prompts, served)["f32"].max()) \
        if served else float("inf")
    return harness.Outcome(attempted=attempted, failed=failed,
                           setup_s=t_first - ctx.t_start,
                           checks={"gap": (gap, t["limits"]["gap"])},
                           values=values, reduced=reduced, counters=counters,
                           memory_peak_bytes=peak,
                           reference_s=time.perf_counter() - t_ref)
