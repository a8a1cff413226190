"""Device time of the train step by stage, for the ``train.<stage>_ms``
readers.

The program names each stage of its step where it runs and keeps, for the
step compiled last, a table from each compiled instruction to its stage
(``repro.core.stages``). A trace's op times (``bench.trace.reduce_events``:
``op_s``, self seconds by instruction name, kernels by their name with the
``.N`` cut off) are summed here by that table. Table names are keyed as
the trace keys them, through ``bench.trace.op_name``; a kernel name whose
calls lie in several stages belongs to none.
"""

from __future__ import annotations

from typing import Dict, Optional

from bench.trace import op_name


def table() -> Optional[Dict]:
    """The program's ``{instruction: (stage, opcode)}`` for the train step
    it compiled last; ``None`` where it keeps none."""
    try:
        from repro.core import stages
    except ImportError:
        return None
    return stages.last_table()


def op_stages(tab: Dict) -> Dict[str, Optional[str]]:
    """``{op key of the trace: stage}``."""
    out: Dict[str, Optional[str]] = {}
    for name, (stage, opcode) in tab.items():
        key, _ = op_name(f"{name} = {opcode}(")
        out[key] = stage if out.get(key, stage) == stage else None
    return out


def stage_seconds(trace, tab: Dict) -> Dict[Optional[str], float]:
    """Device self seconds of the window by stage (``None``: ops the
    table puts in no stage, or does not hold)."""
    stages = op_stages(tab)
    out: Dict[Optional[str], float] = {}
    for key, s in trace["op_s"].items():
        stage = stages.get(key)
        out[stage] = out.get(stage, 0.0) + s
    return out


def read_stage(stage: str, trace, counters) -> Optional[float]:
    """Device ms per step of ``stage``; ``None`` where the program keeps no
    table or no op of the stage ran in the window."""
    tab = table()
    if not tab or not counters.get("steps"):
        return None
    seconds = stage_seconds(trace, tab)
    if stage not in seconds:
        return None
    return 1e3 * seconds[stage] / counters["steps"]
