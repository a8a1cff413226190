"""Run one benchmark cell once and print the result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Everything that belongs to one cell is found by name from ``BENCHMARK.json``:
the cell's traffic file ``bench/traffic/<workload>.json`` names its driver
``bench/drivers/<driver>.py`` (one per program entry that a window drives);
the configuration's ``file`` holds the model; ``bench/metrics/<metric>.py``
reads one per-layer metric. Adding a cell, a configuration or a metric adds
files and entries and edits none.

The run names its device, refuses a device that ``bench/peaks.json`` does
not list (the CPU among them), keeps JAX's compilation cache at a fixed
path inside the checkout, and prints, as the last line of standard output,
one JSON object: ``correct``, ``attempted``, ``failed``, ``metrics``,
``device``, with ``--trace 1`` also ``breakdown``, and last ``checks``,
each compared number beside its limit. The same checks are the last lines
of standard error.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import importlib.util
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path):
    """Import the file at ``path`` (its name may hold dots, as a metric's
    does) as a module of its own, once per process."""
    name = "bench._files." + str(path.resolve().relative_to(BENCH)).replace(
        "/", ".").replace(".py", "").replace(".", "_").replace("-", "_")
    if name in sys.modules:
        return sys.modules[name]
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with what it names."""

    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def find_cell(workload: str, bench_file: Path = ROOT / "BENCHMARK.json"
              ) -> Cell:
    spec = load_json(bench_file)
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise SystemExit(f"unknown workload {workload!r}; known: "
                         f"{sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in spec["configs"]}[w["config"]]

    def applies(m):
        return workload in m.get("workloads", [workload])

    return Cell(
        name=workload, chips=int(w["chips"]),
        config=load_json(ROOT / conf["file"]),
        traffic=load_json(BENCH / "traffic" / f"{w['traffic']}.json"),
        end_to_end=[m for m in spec["end_to_end"] if applies(m)],
        per_layer=[m for m in spec["per_layer"] if applies(m)])


def peaks_for(device_kind: str) -> Dict[str, Any]:
    """The peaks of ``device_kind``; a device not in the table is an
    error, never a default."""
    table = load_json(BENCH / "peaks.json")["devices"]
    if device_kind not in table:
        raise LookupError(f"device kind {device_kind!r} is not in "
                          f"bench/peaks.json (known: {sorted(table)})")
    return table[device_kind]


def enable_compile_cache() -> str:
    """JAX's persistent compilation cache where the program keeps it
    (``<checkout>/.jax_cache``, or where ``JAX_COMPILATION_CACHE_DIR``
    points), with every program written to it, however fast it compiled,
    so that a second run compiles none."""
    import jax
    from repro.utils.compile_cache import enable_compile_cache as enable
    path = enable()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class Window:
    """What a driver hands the harness: the start of the measured window
    (the end of set-up), host spans on the profiler's clock while a trace
    is recorded, and the window's end."""

    def __init__(self, trace_dir: Optional[str]):
        self.trace_dir = trace_dir
        self.start_s: Optional[float] = None
        self._span = None

    def span(self, name: str):
        if self.trace_dir is None:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(f"bench.{name}")

    def begin(self) -> None:
        # what set-up left behind is collected now, and later collections
        # walk only what the window makes
        gc.collect()
        gc.freeze()
        if self.trace_dir is not None:
            import jax
            jax.profiler.start_trace(self.trace_dir)
            self._span = self.span("window")
            self._span.__enter__()
        self.start_s = time.time()

    def end(self) -> None:
        if self.trace_dir is not None:
            import jax
            self._span.__exit__(None, None, None)
            jax.profiler.stop_trace()
        gc.unfreeze()


@dataclasses.dataclass
class Run:
    """What a driver returns: counts, end-to-end metrics by name, the
    numbers its per-layer readers take (``counters``), and the compared
    numbers with their limits."""

    attempted: int
    failed: int
    metrics: Dict[str, float]
    counters: Dict[str, Any]
    checks: Dict[str, Dict[str, float]]
    memory_peak_bytes: int

    @property
    def correct(self) -> bool:
        return all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
                   for c in self.checks.values())


def per_layer(cell: Cell, run: Run, reduced: Dict[str, Any],
              peaks: Dict[str, Any], chips: int) -> Dict[str, Any]:
    """Each of the cell's per-layer metrics, from its reader; a reader that
    finds nothing returns ``None`` and the metric is left out."""
    out = {}
    for m in cell.per_layer:
        reader = load_module(BENCH / "metrics" / f"{m['name']}.py")
        value = reader.read(trace=reduced, counters=run.counters,
                            peaks=peaks, chips=chips)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def result_line(run: Run, metrics: Dict[str, Any], device: Dict[str, Any],
                breakdown: Optional[Dict[str, Any]] = None) -> str:
    line: Dict[str, Any] = {
        "correct": run.correct, "attempted": run.attempted,
        "failed": run.failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = run.checks
    return json.dumps(line)


def execute(cell: Cell, seed: int, seconds: float, trace: bool,
            t0: float, devices, peaks: Dict[str, Any]) -> int:
    """Drive the cell once on ``devices`` and print its result line."""
    from bench import trace as trace_lib

    kind = devices[0].device_kind
    driver = load_module(BENCH / "drivers" / f"{cell.traffic['driver']}.py")
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    try:
        window = Window(trace_dir)
        run = driver.run(cell, seed=seed, seconds=seconds, window=window,
                         devices=devices)
        setup_s = window.start_s - t0
        device = {"platform": devices[0].platform, "kind": kind,
                  "count": len(devices),
                  "memory_peak_bytes": run.memory_peak_bytes}
        breakdown = None
        if trace:
            reduced = trace_lib.reduce_dir(trace_dir, window_name="window")
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
            metrics = per_layer(cell, run, reduced, peaks, len(devices))
            breakdown = {"device_ops": reduced["top_ops"],
                         "idle_gaps": reduced["idle_gaps"]}
        else:
            metrics = {}
            for m in cell.end_to_end:
                value = setup_s if m["name"] == "setup_s" \
                    else run.metrics[m["name"]]
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    finally:
        if trace_dir is not None:
            shutil.rmtree(trace_dir, ignore_errors=True)
    for name, c in run.checks.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(result_line(run, metrics, device, breakdown), flush=True)
    return 0


def main(argv: Optional[List[str]] = None, t0: Optional[float] = None
         ) -> int:
    t0 = time.time() if t0 is None else t0
    p = argparse.ArgumentParser(description="Run one benchmark cell once.")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = p.parse_args(argv)
    cell = find_cell(args.workload)
    try:
        import repro  # noqa: F401  (the system under test)
    except ImportError as e:
        log(f"the program is not in this checkout ({e}); nothing measured")
        return 2

    import jax
    devices = jax.devices()
    platform, kind = devices[0].platform, devices[0].device_kind
    log(f"device: {platform} {kind!r} x{len(devices)}")
    if platform != "tpu":
        log(f"no accelerator: JAX found {platform} ({kind!r}); nothing "
            f"measured")
        return 2
    try:
        peaks = peaks_for(kind)
    except LookupError as e:
        log(str(e))
        return 2
    if len(devices) < cell.chips:
        log(f"{cell.name} needs {cell.chips} chips, found {len(devices)}")
        return 2
    devices = devices[:cell.chips]
    log(f"compilation cache: {enable_compile_cache()}")
    return execute(cell, args.seed, args.seconds, bool(args.trace), t0,
                   devices, peaks)
