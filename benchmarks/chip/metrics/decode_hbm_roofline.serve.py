"""decode_hbm_roofline.serve: the bytes one decode step must move
(``flops.decode_bytes``: every weight of the stack and the head, the valid
KV prefix, the new KV position; mean over a job's steps) at the chip's HBM
peak, over the decode program's measured device time per call.

The decode program is the module ``DECODE_MODULE`` in the trace: the
engine's ``jax.jit`` of a lambda around ``decode_step``.  The reader finds
nothing, and the metric is left out, where that module is absent or ran
another number of times than the traced jobs' decode steps."""

import importlib

DECODE_MODULE = "jit__lambda"


def read(r):
    if r.reduced is None:
        return None
    calls = r.reduced.module_calls.get(DECODE_MODULE, 0)
    if not calls or calls != r.counters.get("decode_calls"):
        return None
    flops = importlib.import_module("benchmarks.chip.flops")
    t = r.traffic
    step_bytes = flops.serve_job_decode_bytes(
        r.model, t["batch"], t["prompt_len"], t["new_tokens"])
    per_call_s = r.reduced.module_s[DECODE_MODULE] / r.reduced.n_devices \
        / calls
    return 100.0 * step_bytes / r.peak["hbm_bytes_per_s"] / per_call_s
