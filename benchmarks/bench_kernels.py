"""Batched robust-aggregation pass benchmarks at grid-engine shapes.

The hot path under test is the one the fused grid engine actually runs: a
``[n_cells * n_seeds, n, d]`` stack of worker gradients reduced to
``[n_cells * n_seeds, d]`` per round, per aggregation rule. For every rule
in ``repro.core.aggregators.KERNEL_RULES`` (plus the NNM pre-aggregation
composition) we time

* the jnp reference path (``use_pallas=False`` — the XLA rules), and
* the dispatch path (``use_pallas=None`` — Pallas kernels on TPU, the same
  jnp rules elsewhere),

warm (compile excluded), and record bytes-moved, achieved GB/s, and the
roofline floor from :func:`repro.launch.roofline.aggregation_roofline`.

Gates (written into ``results/BENCH_kernels.json`` + a repo-root mirror,
like bench_sweep):

* every backend: dispatch parity — the auto path matches the jnp path to
  rtol 1e-5 at every benched shape (on CPU they are the same code path, so
  this is exact; on TPU it is the kernel-vs-XLA parity gate);
* TPU only: the kernel path is never slower than the jnp path at Table-1
  shapes and beats it outright (>1x warm) at ``d >= 1e6`` — on other
  backends the roofline memory-bound floor is recorded instead (the
  "whichever gate is tighter on the available backend" clause of ISSUE 7).

Shapes: Table-1 quadratic grid (B=84 fused lanes, n=13, d=64), CNN-scale
(d=33k), and an LLM-block-scale column (B=8, n=13, d=1,048,576 — the
memory-bound regime the kernels exist for). Interpret-mode timings are
deliberately NOT benched: interpret mode is a correctness tool (see
tests/test_kernels.py) and is orders of magnitude off any real rate.
"""

from __future__ import annotations

import argparse
import json
import os
from typing import Optional

import jax
import jax.numpy as jnp

from benchmarks.common import emit, time_fn
from repro.core import aggregators as G
from repro.kernels.flash_attention import attention_ref
from repro.kernels.randk import block_compress_ref
from repro.launch.roofline import aggregation_roofline, detect_hardware

#: (label, B, n, f, d, iters) — B is the fused n_cells * n_seeds axis.
SHAPES = (
    ("table1", 84, 13, 3, 64, 20),
    ("cnn", 12, 13, 3, 33_450, 10),
    ("llm1m", 8, 13, 3, 1_048_576, 3),
)

RULES = (
    ("cwtm", False),
    ("median", False),
    ("krum", False),
    ("cwtm", True),  # NNM pre-aggregation exercises the pairdist kernel
)


def _batched_agg(name: str, f: int, pre_nnm: bool,
                 use_pallas: Optional[bool]):
    cfg = G.AggregatorConfig(name=name, f=f, pre_nnm=pre_nnm,
                             use_pallas=use_pallas)
    return jax.jit(jax.vmap(G.make_aggregator(cfg)))


def bench_rule(name: str, pre_nnm: bool, *, shape, spec, on_tpu: bool):
    label, b, n, f, d, iters = shape
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (b, n, d), jnp.float32)
    jnp_fn = _batched_agg(name, f, pre_nnm, use_pallas=False)
    auto_fn = _batched_agg(name, f, pre_nnm, use_pallas=None)

    y_jnp, y_auto = jnp_fn(x), auto_fn(x)
    scale = float(jnp.max(jnp.abs(y_jnp))) + 1e-12
    parity = float(jnp.max(jnp.abs(y_jnp - y_auto))) / scale

    us_jnp = time_fn(jnp_fn, x, iters=iters)
    us_auto = time_fn(auto_fn, x, iters=iters)

    rl = aggregation_roofline(batch=b, n=n, d=d, spec=spec)
    bytes_moved = b * (n * d + d) * 4
    gbs = bytes_moved / (us_auto / 1e6) / 1e9
    floor_us = rl.memory_s * 1e6
    rule = f"{name}{'+nnm' if pre_nnm else ''}"
    emit(f"kernels/{rule}/{label}", us_auto,
         f"jnp={us_jnp:.1f}us speedup={us_jnp / us_auto:.2f}x "
         f"GB/s={gbs:.1f} floor={floor_us:.1f}us parity={parity:.1e}")
    return {
        "shape": {"B": b, "n": n, "f": f, "d": d},
        "backend": G.kernel_backend_label(None),
        "jnp_us": us_jnp, "dispatch_us": us_auto,
        "speedup_vs_jnp": us_jnp / us_auto,
        "bytes_moved": bytes_moved, "achieved_gb_s": gbs,
        "roofline_floor_us": floor_us,
        "roofline_bottleneck": rl.bottleneck,
        "floor_ratio": us_auto / floor_us if floor_us > 0 else None,
        "dispatch_parity_rel": parity,
        "parity_ok": bool(parity <= 1e-5),
        # hard perf gates only where the kernel path is live (TPU); on CPU
        # the dispatch path IS the jnp path and timing ratios are noise
        "gated": bool(on_tpu),
    }


def _legacy_micro(results):
    """The pre-PR-7 single-op micro timings, kept for cross-PR trajectory
    (randk compressor + flash-attention reference paths)."""
    key = jax.random.PRNGKey(0)
    d, bs = 1 << 20, 512
    g = jax.random.normal(key, (1, d))
    idx = jnp.arange(0, d // bs, 16, dtype=jnp.int32)
    us = time_fn(jax.jit(lambda a: block_compress_ref(a, idx, bs, 16.0)), g,
                 iters=5)
    emit("kernels/randk_compress_ref/d1M", us, f"k={idx.shape[0] * bs}")
    results["randk_compress_ref_us"] = us

    q = jax.random.normal(key, (1, 1024, 8, 64), jnp.float32)
    k = jax.random.normal(key, (1, 1024, 2, 64), jnp.float32)
    us = time_fn(jax.jit(lambda a, b2: attention_ref(a, b2, b2)), q, k,
                 iters=3)
    emit("kernels/attention_ref/s1024", us, "")
    results["attention_ref_us"] = us
    return results


def run(out: str = "results/BENCH_kernels.json",
        out_root: str = "BENCH_kernels.json",
        hardware: Optional[str] = None):
    spec = detect_hardware(hardware)
    on_tpu = jax.default_backend() == "tpu"
    jnp.zeros(1).block_until_ready()  # backend init outside all timings

    results = {"hardware": spec.name,
               "backend": G.kernel_backend_label(None),
               "aggregation": {}}

    def flush():
        for path in (out, out_root):
            if path:
                os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
                with open(path, "w") as fh:
                    json.dump(results, fh, indent=2)

    failures = []
    try:
        for shape in SHAPES:
            for name, pre in RULES:
                rule = f"{name}{'+nnm' if pre else ''}"
                row = bench_rule(name, pre, shape=shape, spec=spec,
                                 on_tpu=on_tpu)
                results["aggregation"][f"{rule}/{shape[0]}"] = row
                if not row["parity_ok"]:
                    failures.append(
                        f"{rule}/{shape[0]}: dispatch parity "
                        f"{row['dispatch_parity_rel']:.2e} > 1e-5")
                if row["gated"]:
                    # TPU gates: never slower at Table-1, >1x at d >= 1e6
                    if shape[0] == "table1" and row["speedup_vs_jnp"] < 0.95:
                        failures.append(
                            f"{rule}/table1: kernel path slower than jnp "
                            f"({row['speedup_vs_jnp']:.2f}x)")
                    if shape[3] >= 1_000_000 and row["speedup_vs_jnp"] <= 1.0:
                        failures.append(
                            f"{rule}/{shape[0]}: no speedup at d>=1e6 "
                            f"({row['speedup_vs_jnp']:.2f}x)")
        _legacy_micro(results)
        results["gates"] = {"ok": not failures, "failures": failures,
                            "perf_gated": on_tpu}
    finally:
        flush()
    if failures:
        raise SystemExit("bench_kernels gate failures:\n  "
                         + "\n  ".join(failures))
    return results


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--hardware", default=None,
                   choices=[None, "tpu-v5e", "tpu-v4", "tpu-v5p", "tpu-v6e",
                            "cpu"],
                   help="roofline hardware spec override (default: detect "
                        "from the JAX backend)")
    p.add_argument("--out", default="results/BENCH_kernels.json")
    p.add_argument("--out-root", default="BENCH_kernels.json")
    args = p.parse_args(argv)
    return run(out=args.out, out_root=args.out_root, hardware=args.hardware)


if __name__ == "__main__":
    main()
