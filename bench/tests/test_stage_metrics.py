"""The ``train.<stage>_ms`` readers on one recorded step of the cell.

    PYTHONPATH=src:. python -m pytest -q bench/tests

``bench/testdata/stablelm_rosdhb_randk.stages.json`` holds one whole step's
device events from a traced chip run of ``stablelm_rosdhb_randk`` (names cut
to the instruction text up to its operands, which is all that
``bench.trace.op_name`` reads), the program's stage table for that compiled
step, and each stage's seconds in the step, worked out apart from the
readers: every event's duration less its direct children's, added up by
the stage the table gives its instruction name.
"""

from __future__ import annotations

import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import harness, stage_time, trace  # noqa: E402

DATA = harness.BENCH / "testdata" / "stablelm_rosdhb_randk.stages.json"
STAGES = ("grads", "flatten", "sparsify", "attack", "aggregate", "update")


@pytest.fixture(scope="module")
def recorded():
    data = harness.load_json(DATA)
    return data, trace.reduce_events(data["events"])


@pytest.fixture
def table(recorded, monkeypatch):
    tab = {k: tuple(v) for k, v in recorded[0]["table"].items()}
    monkeypatch.setattr(stage_time, "table", lambda: tab)
    return tab


def read(stage, reduced):
    reader = harness.load_module(
        harness.BENCH / "metrics" / f"train.{stage}_ms.py")
    return reader.read(trace=reduced, counters={"steps": 1}, peaks={},
                       chips=1)


@pytest.mark.parametrize("stage", STAGES)
def test_each_reader_gives_the_worked_seconds(recorded, table, stage):
    """To 0.1 us: the worked seconds go by instruction, the trace by
    kernel name, and the step's ``custom-call.N`` calls (0 or 1 ns each,
    10 ns in all), which lie in several stages, go to none in the
    readers."""
    data, reduced = recorded
    want = 1e3 * data["expected"]["stage_s"][stage]
    assert read(stage, reduced) == pytest.approx(want, abs=1e-4)


def test_the_stages_add_up_to_the_busy_time(recorded, table):
    data, reduced = recorded
    busy_ms = 1e3 * data["expected"]["busy_s"]
    assert reduced["busy_s"] == pytest.approx(data["expected"]["busy_s"],
                                              rel=1e-9)
    total = sum(read(stage, reduced) for stage in STAGES)
    assert abs(total - busy_ms) <= 0.02 * busy_ms, (total, busy_ms)


def test_nothing_is_read_without_a_table(recorded, monkeypatch):
    monkeypatch.setattr(stage_time, "table", lambda: None)
    assert all(read(stage, recorded[1]) is None for stage in STAGES)


def test_a_stage_with_no_op_in_the_window_reads_nothing(recorded,
                                                        monkeypatch):
    only_grads = {k: ("grads", v[1])
                  for k, v in recorded[0]["table"].items()}
    monkeypatch.setattr(stage_time, "table", lambda: only_grads)
    assert read("grads", recorded[1]) > 0
    assert read("flatten", recorded[1]) is None


def test_kernels_are_keyed_as_the_trace_keys_them():
    tab = {"attention.4": ("grads", "custom-call"),
           "attention.5": ("grads", "custom-call"),
           "custom-call.25": ("grads", "custom-call"),
           "custom-call.26": ("aggregate", "custom-call"),
           "fusion.7": ("update", "fusion")}
    # a kernel name called in two stages belongs to neither
    assert stage_time.op_stages(tab) == {
        "attention": "grads", "custom-call": None, "fusion.7": "update"}
