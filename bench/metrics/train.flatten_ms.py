"""train.flatten_ms: device ms per step in the train step's ``flatten``
stage: the gradients laid out as the ``[n, D]`` bank (``flatten_to_bank`` or
``stacked_ravel``).

The device self time of the ops that the program's stage table puts in the
stage, summed over the traced window, over the window's steps
(``bench/stage_time.py``).
"""

from bench.stage_time import read_stage


def read(trace, counters, peaks, chips):
    return read_stage("flatten", trace, counters)
