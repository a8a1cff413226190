"""train.update_ms: device ms per step in the train step's ``update`` stage:
the direction unflattened, the SGD step on the f32 master parameters and the
step's outputs.

The device self time of the ops that the program's stage table puts in the
stage, summed over the traced window, over the window's steps
(``bench/stage_time.py``).
"""

from bench.stage_time import read_stage


def read(trace, counters, peaks, chips):
    return read_stage("update", trace, counters)
