"""The training cell's comparison at a size a CPU test holds.

    PYTHONPATH=src python -m pytest -q bench/tests

The cell ``stablelm_rosdhb_randk`` runs here with its published-width
model cut to d_model 256, 2 heads, d_ff 512, vocab 2048 and sequences of
128, on the CPU (the kernels resolve to their XLA paths): the program
passes the cell's limits, the fp8 control fails one of them, and a whole
run with the timed path broken underneath (state left unchanged, half of
each sequence left out of the loss, one leaf's update applied twice)
prints ``correct: false``. The harness's look for a chip is skipped.
"""

from __future__ import annotations

import contextlib
import io
import json
import os
import sys

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402

from bench import calibrate, compare, harness  # noqa: E402

WORKLOAD = "stablelm_rosdhb_randk"
SEEDS = [5, 2 ** 31 + 77, 2 ** 33 + 1]


def small_cell() -> harness.Cell:
    cell = harness.find_cell(WORKLOAD)
    cell.config = dict(cell.config, hidden_size=256, intermediate_size=512,
                       num_attention_heads=2, num_key_value_heads=2,
                       vocab_size=2048)
    cell.traffic = dict(cell.traffic, seq_len=128)
    return cell


@pytest.fixture(scope="module")
def readings():
    return calibrate.calibrate(small_cell(), SEEDS, n_control=len(SEEDS),
                               n_faults=0, devices=jax.devices(),
                               log=lambda *_: None)


def test_program_passes_the_limits(readings):
    limits = small_cell().traffic["limits"]
    for row in readings["rows"]["sound"]:
        for k, limit in limits.items():
            assert row[k] <= limit, (row, k, limit)


def test_control_fails_a_limit(readings):
    limits = small_cell().traffic["limits"]
    for row in readings["rows"]["control"]:
        assert any(row[k] > limit for k, limit in limits.items()), row


def run_line(fault):
    """One whole run of the small cell, with ``fault`` planted; its result
    line."""
    out = io.StringIO()
    peaks = harness.load_json(harness.BENCH / "peaks.json")["devices"]
    with calibrate.planted(fault), contextlib.redirect_stdout(out):
        harness.execute(small_cell(), seed=SEEDS[1], seconds=0.5,
                        trace=False, t0=0.0, devices=jax.devices(),
                        peaks=peaks["TPU v5 lite"])
    return json.loads(out.getvalue().strip().splitlines()[-1])


@pytest.mark.parametrize("fault", [None, "unchanged", "half_batch",
                                   "altered"])
def test_a_broken_step_reads_incorrect(fault):
    line = run_line(fault)
    assert line["correct"] is (fault is None), line["checks"]
    assert list(line)[-1] == "checks"
    assert line["attempted"] >= 1 and line["failed"] == 0


def test_leaves_the_reference_does_not_move_are_left_out():
    keep = compare.kept([1.0, 2.0, 3.0, 1e-4, 0.0])
    assert keep.tolist() == [True, True, True, False, False]
    gap = compare.worst_leaf_gap([1.0, 2.2, 3.0, 5.0, 9.0],
                                 [1.0, 2.0, 3.0, 1e-4, 0.0], keep)
    assert gap == pytest.approx(0.1)
