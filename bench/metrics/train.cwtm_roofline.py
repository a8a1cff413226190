"""train.cwtm_roofline: the CWTM kernel's share of its roofline, in %.

Per step the kernel aggregates the [n, D] momentum bank to [D]: the least
work reads the bank once and writes the aggregate once, (n + 1) D elements
of the bank's dtype, and makes a few comparisons per element, far under the
FLOP peak. Bound by HBM bandwidth: floor = bytes / peak bytes/s. The share
is steps x floor over the kernel's summed device time in the window.
"""

from bench.trace import kernel_seconds

#: The program's names for the kernel, as the trace gives them.
NAMES = ("cwtm",)


def bytes_per_step(s):
    return (s["n_workers"] + 1) * s["D"] * s["bank_bytes"]


def read(trace, counters, peaks, chips):
    busy = kernel_seconds(trace, NAMES)
    if busy <= 0 or not counters.get("steps"):
        return None
    floor = bytes_per_step(counters["shapes"]) / peaks["hbm_bytes_per_s"]
    return 100.0 * counters["steps"] * floor / busy
