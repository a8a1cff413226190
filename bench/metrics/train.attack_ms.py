"""train.attack_ms: device ms per step in the train step's ``attack`` stage:
the Byzantine overwrite of the first f rows (``_byzantine_overwrite``).

The device self time of the ops that the program's stage table puts in the
stage, summed over the traced window, over the window's steps
(``bench/stage_time.py``).
"""

from bench.stage_time import read_stage


def read(trace, counters, peaks, chips):
    return read_stage("attack", trace, counters)
