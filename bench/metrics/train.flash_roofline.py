"""train.flash_roofline: the flash-attention forward kernel's share of its
roofline, in %.

Per step it runs once per sequence (n workers x local batch): causal
attention's two products over the s (s + 1) / 2 query-key pairs of each of
h heads of width dh, 2 x 2 dh FLOPs a pair, and at least q, k, v read and
the output written once (bf16). The floor is the larger of FLOPs over the
bf16 peak and bytes over the HBM peak; the share is steps x floor over the
kernel's summed device time in the window. The backward pass is XLA's and
is not this kernel's.
"""

from bench.trace import kernel_seconds

#: The program's names for the kernel, as the trace gives them.
NAMES = ("attention",)


def flops_per_step(s):
    seqs = s["n_workers"] * s["local_batch"]
    dh = s["d_model"] // s["n_heads"]
    pairs = s["seq_len"] * (s["seq_len"] + 1) // 2
    return seqs * s["n_layers"] * 4 * dh * s["n_heads"] * pairs


def bytes_per_step(s):
    seqs = s["n_workers"] * s["local_batch"]
    dh = s["d_model"] // s["n_heads"]
    q_o = 2 * s["seq_len"] * s["n_heads"] * dh
    k_v = 2 * s["seq_len"] * s["n_kv_heads"] * dh
    return seqs * s["n_layers"] * (q_o + k_v) * 2


def read(trace, counters, peaks, chips):
    busy = kernel_seconds(trace, NAMES)
    if busy <= 0 or not counters.get("steps"):
        return None
    s = counters["shapes"]
    floor = max(flops_per_step(s) / peaks["bf16_flops"],
                bytes_per_step(s) / peaks["hbm_bytes_per_s"])
    return 100.0 * counters["steps"] * floor / busy
