"""train.sparsify_ms: device ms per step in the train step's ``sparsify``
stage: Block-RandK: the round's masks, compress and decompress, the unbiased
reconstruction and the rows written back (``compressed_estimate``).

The device self time of the ops that the program's stage table puts in the
stage, summed over the traced window, over the window's steps
(``bench/stage_time.py``).
"""

from bench.stage_time import read_stage


def read(trace, counters, peaks, chips):
    return read_stage("sparsify", trace, counters)
