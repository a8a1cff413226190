"""Self-check of the benchmark's yardstick, on the CPU.

    PYTHONPATH=src python -m bench.selfcheck

1. The trace reduction on a trace made by hand, and on a slice of the
   first traced chip run of ``stablelm_rosdhb_randk`` kept in
   ``bench/testdata``, against numbers worked by hand.
2. The FLOP and byte functions of the per-layer readers at the cell's
   shapes (stablelm_3b at 2 layers: 6 N + 6 L s d = 1.787 GFLOP per token,
   (4 + 1) x 416.18M x 2 B = 4.16 GB for the CWTM pass over the bank) and
   at 1 layer (1.280 GFLOP per token, 3.37 GB).
3. The peaks table: TPU v5e at 197 TFLOP/s bf16 and 819 GB/s, and no entry
   for the CPU.

Exits 1 and names the failed check when one fails.
"""

from __future__ import annotations

import math
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from bench import harness, trace  # noqa: E402

TESTDATA = harness.BENCH / "testdata"


def close(a: float, b: float, rel: float = 1e-9) -> bool:
    return math.isclose(a, b, rel_tol=rel, abs_tol=1e-15)


def check_hand_trace() -> None:
    """A ``while`` op holding a kernel call and a fusion, a second kernel
    after a gap, and a copy, in a 40 ns window; the host waits during the
    first gap and makes a batch during the second."""
    cc = "custom-call(bf16[8] %p), custom_call_target=\"tpu_custom_call\""
    ev = {"devices": {"/device:TPU:0": [
        ["%while.1 = (s32[]) while((s32[]) %t), body=%b", 0.0, 12.0],
        ["%decompress.3 = bf16[8] " + cc, 2.0, 4.0],
        ["%fusion.2 = bf16[8] fusion(bf16[8] %x), kind=kLoop", 6.0, 4.0],
        ["%cwtm.1 = bf16[8] " + cc, 20.0, 10.0],
        ["%copy.4 = bf16[8] copy(bf16[8] %y)", 38.0, 1.0],
        ["%copy.5 = bf16[8] copy(bf16[8] %y)", 41.0, 1.0]]},
        "host": [["bench.window", 0.0, 40.0], ["bench.wait", 11.0, 10.0],
                 ["bench.batch", 30.0, 8.0]]}
    r = trace.reduce_events(ev)
    # busy: [0, 12] + [20, 30] + [38, 39] = 23 ns of 40; copy.5 is after
    assert close(r["busy_s"], 23e-9), r["busy_s"]
    assert close(r["window_s"], 40e-9), r["window_s"]
    # self time: the while op less its body's 4 + 4 ns
    want = {"while.1": 4e-9, "decompress": 4e-9, "fusion.2": 4e-9,
            "cwtm": 10e-9, "copy.4": 1e-9}
    assert r["op_s"].keys() == want.keys(), r["op_s"]
    assert all(close(r["op_s"][k], v) for k, v in want.items()), r["op_s"]
    assert r["kernel_s"].keys() == {"decompress", "cwtm"}, r["kernel_s"]
    assert close(trace.kernel_seconds(r, ("cwtm", "decompress")), 14e-9)
    assert [g[0] for g in r["idle_gaps"]] == [
        "bench.wait", "bench.batch", "host: no bench span"], r["idle_gaps"]
    assert [round(g[1] * 1e9, 6) for g in r["idle_gaps"]] == [8, 8, 1]
    assert trace.merge([(3, 4), (0, 2), (1, 3)]) == [(0, 4)]


def check_recorded_trace() -> None:
    """The kept slice of the first traced chip run: the numbers in its
    ``expected`` entry were worked by hand from its events."""
    data = harness.load_json(TESTDATA / "stablelm_rosdhb_randk.trace.json")
    r = trace.reduce_events(data["events"])
    want = data["expected"]
    for key in ("busy_s", "window_s"):
        assert close(r[key], want[key], 1e-6), (key, r[key], want[key])
    for name, seconds in want["kernel_s"].items():
        assert close(r["kernel_s"][name], seconds, 1e-6), \
            (name, r["kernel_s"][name])
    assert [g[0] for g in r["idle_gaps"][:len(want["gap_labels"])]] == \
        want["gap_labels"], r["idle_gaps"]


#: Worked by hand for stablelm_3b at its widths: D (the flat vector,
#: padded to 512-blocks), the matmul parameters N a token passes through,
#: and the GFLOP a token and the GB of a CWTM pass, rounded as printed.
BY_DEPTH = {
    1: {"D": 336_870_400, "N": 208_076_800, "gflop": 1.280, "cwtm_gb": 3.37},
    2: {"D": 416_179_200, "N": 287_375_360, "gflop": 1.787, "cwtm_gb": 4.16},
}


def check_flops_and_bytes() -> None:
    cell = harness.find_cell("stablelm_rosdhb_randk")
    assert cell.config["num_hidden_layers"] in BY_DEPTH
    driver = harness.load_module(harness.BENCH / "drivers" / "train_step.py")
    mfu = harness.load_module(harness.BENCH / "metrics" / "train.mfu.py")
    cwtm = harness.load_module(
        harness.BENCH / "metrics" / "train.cwtm_roofline.py")
    flash = harness.load_module(
        harness.BENCH / "metrics" / "train.flash_roofline.py")
    randk = harness.load_module(
        harness.BENCH / "metrics" / "train.randk_roofline.py")
    for layers, want in BY_DEPTH.items():
        cell.config = dict(cell.config, num_hidden_layers=layers)
        s = driver.shapes(cell)
        assert s["D"] == want["D"], (layers, s["D"])
        per_token = mfu.flops_per_token(s)
        assert close(per_token, 6 * want["N"] + 6 * layers * 2048 * 2560), \
            (layers, per_token)
        assert round(per_token / 1e9, 3) == want["gflop"], per_token
        assert cwtm.bytes_per_step(s) == 5 * want["D"] * 2
        assert round(cwtm.bytes_per_step(s) / 1e9, 2) == want["cwtm_gb"]
        # 4 sequences x 32 heads x 2048 * 2049 / 2 pairs x 2 products x 2
        # x 80, in each layer
        assert flash.flops_per_step(s) == \
            layers * 4 * 32 * (2048 * 2049 // 2) * 4 * 80
        kb = round(0.05 * (want["D"] // 512))
        assert s["k_blocks"] == kb, (layers, s["k_blocks"])
        assert randk.bytes_per_step(s) == 4 * (
            (3 * kb * 512 + want["D"]) * 2 + 4 * kb)


def check_peaks() -> None:
    p = harness.peaks_for("TPU v5 lite")
    assert p["bf16_flops"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    try:
        harness.peaks_for("cpu")
    except LookupError:
        return
    raise AssertionError("the CPU has peaks")


CHECKS = [check_hand_trace, check_recorded_trace, check_flops_and_bytes,
          check_peaks]


def main() -> int:
    failed = []
    for check in CHECKS:
        try:
            check()
        except (AssertionError, OSError, KeyError) as e:
            failed.append(check.__name__)
            print(f"FAIL {check.__name__}: {e!r}", file=sys.stderr)
        else:
            print(f"ok   {check.__name__}")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.path.insert(0, str(harness.ROOT / "src"))
    sys.exit(main())
