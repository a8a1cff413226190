"""train.randk_roofline: the Block-RandK kernels' share of their roofline,
in %.

Per step each of the n workers' gradient rows makes one round trip:
compress gathers the k selected blocks and scales them (reads k B, writes
k B elements) and decompress scatters them into a zeroed dense row (reads
k B, writes D). The least work is each input read once and each output
written once: n (3 k B + D) elements of the bank's dtype, plus the k block
ids. Memory bound: floor = bytes / peak bytes/s; the share is steps x floor
over the kernels' summed device time in the window.
"""

from bench.trace import kernel_seconds

#: The program's names for the kernels, as the trace gives them.
NAMES = ("compress", "decompress")


def bytes_per_step(s):
    kb_elems = s["k_blocks"] * s["block_size"]
    per_row = (3 * kb_elems + s["D"]) * s["bank_bytes"] + 4 * s["k_blocks"]
    return s["n_workers"] * per_row


def read(trace, counters, peaks, chips):
    busy = kernel_seconds(trace, NAMES)
    if busy <= 0 or not counters.get("steps"):
        return None
    floor = bytes_per_step(counters["shapes"]) / peaks["hbm_bytes_per_s"]
    return 100.0 * counters["steps"] * floor / busy
