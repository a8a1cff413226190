"""train.aggregate_ms: device ms per step in the train step's ``aggregate``
stage: per-worker momentum and the robust rule over the bank
(``_rosdhb_apply``).

The device self time of the ops that the program's stage table puts in the
stage, summed over the traced window, over the window's steps
(``bench/stage_time.py``).
"""

from bench.stage_time import read_stage


def read(trace, counters, peaks, chips):
    return read_stage("aggregate", trace, counters)
