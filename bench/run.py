#!/usr/bin/env python3
"""Command of the benchmark: run one cell once (see ``bench/harness.py``).

    python3 bench/run.py --workload stablelm_rosdhb_randk --seed 7 \
        --seconds 10 --trace 0
"""

import os
import sys
import time


def _process_start() -> float:
    """Wall-clock time at which this process started (Linux ``/proc``);
    set-up is counted from there. Elsewhere, from this line."""
    try:
        with open("/proc/self/stat") as f:
            ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.time() - (uptime - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return time.time()


T0 = _process_start()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402

if __name__ == "__main__":
    sys.exit(harness.main(sys.argv[1:], t0=T0))
