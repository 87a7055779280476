"""mfu.serve: the model operations of the traced jobs (``flops.py``: every
prefill position through the stack, the head on the last one, then each
decode step with attention over its cache) over the traced window, as a
share of the chips' bf16 peak."""


def read(r):
    if r.reduced is None or not r.counters.get("jobs"):
        return None
    achieved = r.counters["job_flops"] * r.counters["jobs"] \
        / r.reduced.window_s
    return 100.0 * achieved / (r.chips * r.peak["bf16_flops_per_s"])
