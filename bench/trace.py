"""Reduce a profiler trace of the measured window to the numbers the
per-layer readers and the result line take.

- Busy time is the union of the intervals in which an operation ran on a
  device (its "XLA Ops" line), clipped to the window, averaged over the
  devices; the idle share is 1 - busy / window.
- Op time is summed by the name the trace gives each operation, as self
  time (a ``while`` op less the ops of its body); a kernel's time (a
  ``custom-call``) is summed under the program's name for it.
- Idle gaps between device operations are named by the benchmark's own
  host span (``bench.*``) that covers most of the gap.

A trace is read with ``jax.profiler.ProfileData`` and first turned into a
plain dict of events (:func:`events_of`), which is what
``bench/testdata`` keeps and :func:`reduce_events` takes.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Any, Dict, List, Tuple

Interval = Tuple[float, float]
#: The device line that holds one event per operation run.
OPS_LINE = "XLA Ops"
#: Entries of each list of ``breakdown``.
TOP = 10


def events_of(path: str) -> Dict[str, Any]:
    """``{"devices": {plane: [[name, start_ns, dur_ns], ...]}, "host":
    [[name, start_ns, dur_ns], ...]}`` from one ``.xplane.pb``: each
    device's operations, and the host's ``bench.*`` spans."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[str, List] = {}
    host: List = []
    for plane in data.planes:
        if plane.name.startswith("/device:") and \
                not plane.name.startswith("/device:CPU"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[plane.name] = [
                        [e.name, float(e.start_ns), float(e.duration_ns)]
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([e.name, float(e.start_ns), float(e.duration_ns)]
                            for e in line.events
                            if e.name.startswith("bench."))
    return {"devices": devices, "host": host}


def find_trace(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def merge(intervals: List[Interval]) -> List[Interval]:
    """The union of ``intervals`` as sorted, disjoint intervals."""
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals: List[Interval], lo: float, hi: float) -> List[Interval]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if min(b, hi) > max(a, lo)]


def _label(gap: Interval, spans: List[Tuple[str, float, float]]) -> str:
    best, cover = "host: no bench span", 0.0
    for name, a, b in spans:
        c = min(b, gap[1]) - max(a, gap[0])
        if c > cover:
            best, cover = name, c
    return best


def op_name(event_name: str) -> Tuple[str, bool]:
    """``("decompress", True)`` for a kernel (a ``custom-call``), whose
    calls are summed under the name of the program's kernel;
    ``("fusion.437", False)`` for any other op, by its HLO instruction."""
    short = event_name.split(" = ")[0].lstrip("%")
    if "custom-call(" in event_name:
        return re.sub(r"\.\d+$", "", short), True
    return short, False


def self_times(events: List[List]) -> List[float]:
    """Each event's duration less the time of the events nested in it (a
    ``while`` op holds the ops of its body)."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    self_ns = [float(e[2]) for e in events]
    stack: List[int] = []
    for i in order:
        s = events[i][1]
        while stack and events[stack[-1]][1] + events[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            self_ns[stack[-1]] -= events[i][2]
        stack.append(i)
    return self_ns


def reduce_events(ev: Dict[str, Any], window_name: str = "window"
                  ) -> Dict[str, Any]:
    """Inside the host span ``bench.<window_name>`` (the whole trace where
    it is missing): busy and window seconds; each op's self seconds (by
    :func:`op_name`); each kernel's seconds; the top ops by self time and
    the longest idle gaps. Times are averaged over the devices."""
    spans = [(n, s, s + d) for n, s, d in ev["host"]]
    windows = [(s, e) for n, s, e in spans if n == f"bench.{window_name}"]
    devices = ev["devices"]
    if not devices:
        raise ValueError("the trace holds no device operations")
    if windows:
        lo, hi = windows[0]
    else:
        lo = min(s for evs in devices.values() for _, s, _ in evs)
        hi = max(s + d for evs in devices.values() for _, s, d in evs)
    inner = [(n, a, b) for n, a, b in spans
             if n != f"bench.{window_name}"]
    op_ns: Dict[str, float] = defaultdict(float)
    kernel_ns: Dict[str, float] = defaultdict(float)
    busy_ns, gaps = 0.0, []
    for evs in devices.values():
        busy = merge(clip([(s, s + d) for _, s, d in evs], lo, hi))
        # an op is counted whole in the window that holds its midpoint
        evs = [e for e in evs if lo <= e[1] + e[2] / 2 <= hi]
        for e, own in zip(evs, self_times(evs)):
            name, kernel = op_name(e[0])
            op_ns[name] += own
            if kernel:
                kernel_ns[name] += e[2]
        busy_ns += sum(b - a for a, b in busy)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        gaps.extend((edges[i], edges[i + 1])
                    for i in range(0, len(edges), 2)
                    if edges[i + 1] > edges[i])
    n_dev = len(devices)
    gaps.sort(key=lambda g: g[1] - g[0], reverse=True)
    top = sorted(op_ns.items(), key=lambda kv: kv[1], reverse=True)
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_ns / n_dev * 1e-9,
        "op_s": {k: v / n_dev * 1e-9 for k, v in op_ns.items()},
        "kernel_s": {k: v / n_dev * 1e-9 for k, v in kernel_ns.items()},
        "top_ops": [[k, v / n_dev * 1e-9] for k, v in top[:TOP]],
        "idle_gaps": [[_label(g, inner), (g[1] - g[0]) * 1e-9]
                      for g in gaps[:TOP]],
    }


def reduce_dir(trace_dir: str, window_name: str = "window"
               ) -> Dict[str, Any]:
    return reduce_events(events_of(find_trace(trace_dir)), window_name)


def kernel_seconds(reduced: Dict[str, Any], names) -> float:
    """Device seconds of the kernels called ``names``."""
    return sum(reduced["kernel_s"].get(n, 0.0) for n in names)
