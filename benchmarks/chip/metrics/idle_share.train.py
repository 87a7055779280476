"""idle_share.train: the share of the traced window in which no operation
ran on the device (the union of the device's operation intervals is busy;
mean over the chips)."""


def read(r):
    if r.reduced is None or not r.reduced.busy_s:
        return None
    return 100.0 * (1.0 - r.reduced.busy_mean_s / r.reduced.window_s)
