"""train.grads_ms: device ms per step in the train step's ``grads`` stage:
the per-worker forward and backward passes (``vmap(value_and_grad)``) and
the bf16 cast of the parameters.

The device self time of the ops that the program's stage table puts in the
stage, summed over the traced window, over the window's steps
(``bench/stage_time.py``).
"""

from bench.stage_time import read_stage


def read(trace, counters, peaks, chips):
    return read_stage("grads", trace, counters)
