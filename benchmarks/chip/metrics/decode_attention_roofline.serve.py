"""decode_attention_roofline.serve: the attention K and V that one decode
step must read (the family's ``decode_kv_bytes``: every invocation's valid
prefix, each row; mean over a job's steps) at the chip's HBM peak, over the
device time per decode call of the flash-decode kernel.

The kernel's time is that of the operations of the decode module
``DECODE_MODULE`` whose names start with ``KERNEL`` (its trace and HLO
name, ``repro.kernels.decode_attention.NAME``), every call of them.  The
reader finds nothing, and the metric is left out, where the family file
counts no ``decode_kv_bytes``, where the kernel did not run, or where the
decode module ran another number of times than the traced jobs' decode
steps."""

import importlib

DECODE_MODULE = "jit__lambda"
KERNEL = "decode_attention_stacked"
CACHE_BYTES = 2  # bfloat16, as the cell serves


def read(r):
    if r.reduced is None:
        return None
    calls = r.reduced.module_calls.get(DECODE_MODULE, 0)
    if not calls or calls != r.counters.get("decode_calls"):
        return None
    harness = importlib.import_module("benchmarks.chip.harness")
    count = getattr(harness.family(r.model), "decode_kv_bytes", None)
    prefix = f"{DECODE_MODULE}/{KERNEL}"
    kernel_s = sum(s for op, s in r.reduced.op_s.items()
                   if op.startswith(prefix))
    if count is None or not kernel_s:
        return None
    t = r.traffic
    steps = [t["batch"] * count(r.model, t["prompt_len"] + i + 1, CACHE_BYTES)
             for i in range(t["new_tokens"] - 1)]
    per_call_s = kernel_s / r.reduced.n_devices / calls
    return 100.0 * sum(steps) / len(steps) / r.peak["hbm_bytes_per_s"] \
        / per_call_s
