"""train.mfu: the train step's model FLOP/s over the chips' bf16 peak, in %.

Model FLOPs per token are 6 N + 6 L s d_attn: N counts the matmul
parameters a token passes through (every layer's attention and MLP
projections and the LM head; the embedding lookup is no matmul), and the
second term is causal attention's two products, forward and backward.
Recomputation is not counted. Tokens and seconds are those of the traced
window, so the rate is the traced run's own.
"""


def flops_per_token(s):
    d, dff, h, kv = s["d_model"], s["d_ff"], s["n_heads"], s["n_kv_heads"]
    dh = d // h
    attn = d * h * dh * 2 + d * kv * dh * 2
    mlp = 3 * d * dff
    n = s["n_layers"] * (attn + mlp) + d * s["vocab"]
    return 6 * n + 6 * s["n_layers"] * s["seq_len"] * h * dh


def read(trace, counters, peaks, chips):
    if not counters.get("steps"):
        return None
    rate = counters["tokens"] / counters["window_s"]
    return 100.0 * flops_per_token(counters["shapes"]) * rate / (
        chips * peaks["bf16_flops"])
