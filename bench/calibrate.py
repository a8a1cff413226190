#!/usr/bin/env python3
"""Readings that the limits of a training cell are set from, in one process.

    python3 bench/calibrate.py --workload stablelm_rosdhb_randk \
        --seeds 11,12,13 --control 3 --faults 3 --out readings.json

For each seed: the program's readings over the checked rounds (the run's
own set-up path: the compiled step, driven from the seed) against the plain
reference. For the first ``--control`` seeds, the control: the reference
computed in fp8 (per-tensor scaled e4m3) in the program's place. For the
first ``--faults`` seeds, the program with each planted fault: a step that
returns its state unchanged, half of each sequence left out of the loss
(the mean taken over the rest), and one leaf's update applied twice (an
answer altered where it is produced).

The benchmark's runs do not run this; it sets and checks the limits, and
``bench/tests`` runs it at a size a CPU test holds.
"""

from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time
from typing import Any, Dict, List, Optional

if __name__ == "__main__":
    _ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path[:0] = [_ROOT, os.path.join(_ROOT, "src")]

import jax

from bench import compare, harness

#: The leaf whose update the "altered answer" fault applies twice.
ALTERED_LEAF = "lm_head"


@contextlib.contextmanager
def planted(fault: Optional[str]):
    """The program with ``fault`` planted for the duration."""
    if fault is None:
        yield
        return
    import repro.launch.train as train_mod
    import repro.models.transformer as tf
    saved_build, saved_loss = train_mod.build_train_step, tf.lm_loss

    if fault == "half_batch":
        def lm_loss(params, cfg, batch, **kw):
            s = batch["tokens"].shape[-1]
            return saved_loss(params, cfg,
                              {"tokens": batch["tokens"][..., :s // 2]}, **kw)
        tf.lm_loss = lm_loss
    elif fault in ("unchanged", "altered"):
        def build(plan, mesh):
            step = saved_build(plan, mesh)

            def broken(state, batch):
                new, metrics = step(state, batch)
                if fault == "unchanged":
                    return state, metrics
                params = dict(new.params)
                old = state.params[ALTERED_LEAF]
                params[ALTERED_LEAF] = old + 2.0 * (params[ALTERED_LEAF] - old)
                return new._replace(params=params), metrics
            return broken
        train_mod.build_train_step = build
    else:
        raise ValueError(f"unknown fault {fault!r}")
    try:
        yield
    finally:
        train_mod.build_train_step, tf.lm_loss = saved_build, saved_loss


def program_readings(cell: harness.Cell, driver, seeds: List[int], devices,
                     fault: Optional[str] = None) -> List[Dict[str, Any]]:
    with planted(fault):
        prog = driver.build(cell, devices)
    out = []
    for seed in seeds:
        state = driver.initial_state(prog, seed)
        state, readings = driver.checked_steps(prog, state, seed)
        del state
        out.append(readings)
    del prog
    return out


def _lists(readings: Dict[str, Any]) -> Dict[str, Any]:
    """The per-step and per-leaf readings, without the sampled rows."""
    return {k: v for k, v in readings.items() if k != "first_rows"}


def calibrate(cell: harness.Cell, seeds: List[int], n_control: int,
              n_faults: int, devices, log=harness.log) -> Dict[str, Any]:
    driver = harness.load_module(
        harness.BENCH / "drivers" / f"{cell.traffic['driver']}.py")
    ref_mod = driver.reference(cell)
    rows: Dict[str, List] = {"sound": [], "control": []}
    raw: Dict[str, Dict[str, Any]] = {}
    t0 = time.perf_counter()
    sound = program_readings(cell, driver, seeds, devices)
    refs = []
    for seed, readings in zip(seeds, sound):
        t_ref = time.perf_counter()
        ref = driver.reference_readings(cell, seed)
        log(f"reference for seed {seed}: {time.perf_counter() - t_ref:.1f} s")
        refs.append(ref)
        gaps = compare.training_gaps(readings, ref)
        rows["sound"].append({"seed": seed, **gaps})
        raw[str(seed)] = {"program": _lists(readings),
                          "reference": _lists(ref)}
        log(f"sound seed {seed}: {gaps}")
    for seed, ref in zip(seeds[:n_control], refs):
        ctl = driver.reference_readings(cell, seed, rnd=ref_mod.fp8_round)
        gaps = compare.training_gaps(ctl, ref)
        rows["control"].append({"seed": seed, **gaps})
        raw[str(seed)]["control"] = _lists(ctl)
        log(f"control seed {seed}: {gaps}")
    for fault in ("unchanged", "half_batch", "altered"):
        if not n_faults:
            break
        rows[fault] = []
        got = program_readings(cell, driver, seeds[:n_faults], devices,
                               fault=fault)
        for seed, readings, ref in zip(seeds, got, refs):
            gaps = compare.training_gaps(readings, ref)
            rows[fault].append({"seed": seed, **gaps})
            log(f"fault {fault} seed {seed}: {gaps}")
    summary = {}
    for kind, rs in rows.items():
        if rs:
            summary[kind] = {k: [min(r[k] for r in rs), max(r[k] for r in rs)]
                             for k in rs[0] if k != "seed"}
    leaves = jax.tree_util.tree_flatten_with_path(driver._abstract(cell))[0]
    return {"workload": cell.name, "device": devices[0].device_kind,
            "seconds": time.perf_counter() - t0, "rows": rows,
            "summary": summary,
            "leaves": [jax.tree_util.keystr(p) for p, _ in leaves],
            "readings": raw}


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True,
                   help="comma-separated seeds of the sound runs")
    p.add_argument("--control", type=int, default=3)
    p.add_argument("--faults", type=int, default=3)
    p.add_argument("--out", default=None)
    args = p.parse_args(argv)
    cell = harness.find_cell(args.workload)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        harness.log(f"no accelerator ({devices[0].platform}); nothing run")
        return 2
    harness.enable_compile_cache()
    seeds = [int(s) for s in args.seeds.split(",")]
    result = calibrate(cell, seeds, args.control, args.faults,
                       devices[:cell.chips])
    text = json.dumps(result, indent=1)
    if args.out:
        os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
        with open(args.out, "w") as f:
            f.write(text)
    print(json.dumps(result["summary"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
