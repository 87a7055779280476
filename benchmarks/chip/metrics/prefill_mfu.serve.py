"""prefill_mfu.serve: the operations of one prefill (``flops.prefill_flops``:
every prompt position through the stack, the head on the last one) over
the prefill program's measured device time per call, as a share of the
chips' bf16 peak.

The prefill program is the module ``PREFILL_MODULE`` in the trace: the
engine's ``jax.jit`` of ``repro.models.prefill``.  The reader finds
nothing, and the metric is left out, where that module is absent (a program
that runs prefill eagerly) or ran another number of times than the traced
jobs (one wave each)."""

import importlib

PREFILL_MODULE = "jit_prefill"


def read(r):
    if r.reduced is None:
        return None
    calls = r.reduced.module_calls.get(PREFILL_MODULE, 0)
    if not calls or calls != r.counters.get("jobs"):
        return None
    flops = importlib.import_module("benchmarks.chip.flops")
    t = r.traffic
    per_call_flops = flops.prefill_flops(r.model, t["batch"], t["prompt_len"])
    per_call_s = r.reduced.module_s[PREFILL_MODULE] / r.reduced.n_devices \
        / calls
    return 100.0 * per_call_flops / per_call_s \
        / (r.chips * r.peak["bf16_flops_per_s"])
