"""mfu.train: the model operations of the traced steps (``flops.py``, no
recomputation) over the traced window, as a share of the chips' bf16 peak."""


def read(r):
    if r.reduced is None or not r.counters.get("tokens"):
        return None
    achieved = r.counters["flops_per_token"] * r.counters["tokens"] \
        / r.reduced.window_s
    return 100.0 * achieved / (r.chips * r.peak["bf16_flops_per_s"])
