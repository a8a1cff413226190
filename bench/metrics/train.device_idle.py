"""train.device_idle: the share of the traced window in which no operation
ran on the device, in % (1 - busy / window; busy is the union of the
device's op intervals, averaged over the chips)."""


def read(trace, counters, peaks, chips):
    if not trace or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
