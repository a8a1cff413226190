"""Smoke run of the robust compressed round on one TPU chip.

    python3 chip_smoke.py             # one chip: phases 1-4
    python3 chip_smoke.py --chips 4   # a four-chip host: the two paths that
                                      # exist only across chips

Phases on one chip, in one process:

1. paper grid — ``repro.core.sweep.main`` on the MNIST testbed (n_honest 10,
   f 3; alie,foe,signflip x cwtm,median; 2 seeds), per algorithm at
   :data:`GRID_STEPS` rounds (rosdhb 20, dasha 10), once with
   ``--kernels auto`` and once with ``--kernels jnp``: every cell's final
   loss agrees to :data:`GRID_RTOL` and the auto run's aggregation
   resolves to ``pallas``;
2. kernel parity at benchmark widths — every Pallas kernel of the main path
   against its ``ref.py`` oracle, compiled, within :data:`F32_REL`
   (flash attention in bf16 within :data:`BF16_ATOL`);
3. the trainer at published widths — ``repro.launch.train.train`` with
   ``stablelm_3b`` cut in depth only (:data:`TRAIN_LAYERS`), 4 workers,
   f 1, CWTM, Block-RandK 0.05 / 512, 3 steps, against the same run on the
   XLA paths: finite losses, the step-0 hidden state, the step-0 loss and
   the parameter update after 3 steps within their bounds;
4. the parameter server — ``python -m repro.serve``'s ``main`` on the
   ``stateless-linear`` scenario, cell ``rosdhb/foe/median``: one trace.

With ``--chips 4``: the trainer on a ``(4, 1)`` mesh with the sharded bank
flatten against the same round on one of the devices (parameter updates
after 2 steps), plus the sharded flatten with Block-RandK; and the MNIST
grid with ``--shard`` against ``--no-shard`` (identical rows).

Each bounded check prints its reading beside a broken control (a wrong
attention mask, an update shifted by one block) and fails unless the
reading is within the bound and the control is not. Every phase runs; the
script fails if any did.

Compile and step seconds are printed as smoke timings, not benchmark
numbers. The last line of standard output is one JSON object naming the
device; it is printed only when every phase passed. Without a TPU the script
exits non-zero before any phase runs.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import time
import traceback
from typing import Dict, List, Optional, Sequence

REPO = os.path.dirname(os.path.abspath(__file__))

#: Relative bound of an f32 kernel against its oracle: the dispatch-parity
#: bound of benchmarks/bench_kernels.py, max |kernel - ref| / max |ref|.
F32_REL = 1e-5
#: Absolute bound of bf16 flash attention on unit-normal inputs against the
#: f32 oracle (the bf16 bound of the interpret-mode sweep in
#: tests/test_kernels.py).
BF16_ATOL = 5e-2
#: Relative bound between step-0 losses of the kernel and XLA paths of the
#: bf16 model (CPU reading 7.3e-6). At initialisation the loss is about
#: ln(vocab) whatever attention computes, so the hidden-state check below
#: is the one that sees attention.
LOSS_RTOL = 1e-3
#: Relative l2 bound between the step-0 last hidden states of the bf16
#: model with flash attention and with the XLA attention. CPU readings
#: (interpret-mode kernel, 1 layer, d_model 512, seq 256): 8.1e-3, and
#: 0.84 for the control, a sliding window of seq / 32 (a wrong mask).
HIDDEN_L2_REL = 2e-2
#: Relative l2 bound between the parameter updates of the kernel and XLA
#: train runs after phase 3's steps. CPU readings as above: 1.06e-2, and
#: 1.41 for the control, the update shifted by one 512 block.
UPDATE_L2_REL = 5e-2
#: Relative l2 bound between the parameter updates of the four-chip
#: sharded flatten and the one-device round: the same arithmetic, placed
#: differently, so only fusion may round the bf16 gradients differently.
#: CPU reading on four virtual devices: 0.0; control 1.31.
FLATTEN_L2_REL = 1e-2
#: Relative bound between the grid's final losses on the two kernel paths.
GRID_RTOL = 1e-3
#: Depth of stablelm_3b on one v5e chip: the deepest whose train step
#: (4 workers, seq 2048, local batch 1, bf16 momentum bank and server
#: math), by ``compiled.memory_analysis()`` on a described v5e, leaves 2 GB
#: of the 16 GB free — found by :func:`fit_depth`.
TRAIN_LAYERS = 1
HBM_BYTES = 16 * 10 ** 9
MIN_FREE_BYTES = 2 * 10 ** 9


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def timing(phase: str, **seconds) -> None:
    parts = " ".join(f"{k}={v:.3f}s" for k, v in seconds.items())
    log(f"smoke timing (not a benchmark number) {phase}: {parts}")


def rel_err(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / (np.max(np.abs(want)) + 1e-12))


# --------------------------------------------------------------------------
# phase 1: the paper grid through the sweep CLI
# --------------------------------------------------------------------------


def grid_args(algos: str, steps: int, seeds: int, n_honest: int, f: int,
              extra: Sequence[str] = ()) -> List[str]:
    return ["--testbed", "mnist", "--n-honest", str(n_honest),
            "--f", str(f), "--algos", algos,
            "--attacks", "alie,foe,signflip", "--aggs", "cwtm,median",
            "--seeds", str(seeds), "--steps", str(steps), *extra]


def _by_cell(rows) -> Dict:
    return {(r["scenario"], r["seed"]): r for r in rows}


def _labels(rows, field: str) -> List[str]:
    return sorted({r[field] for r in rows})


#: Rounds of the kernel-vs-jnp grid per algorithm. The RoSDHB cells agree
#: to 1.1e-7 after 20 rounds. The Byz-DASHA-PAGE cells amplify last-bit
#: differences roughly tenfold every two to three rounds: on the CPU the
#: worst DASHA cell differs by 2.2e-6 after 10 rounds, 8.9e-4 after 15 and
#: 5.8e-3 after 20, where the jnp path differs from itself by 4.7e-3
#: between its fused and unfused programs. At 10 rounds their comparison
#: is about the kernels, not about that amplification.
GRID_STEPS = {"rosdhb": 20, "dasha": 10}


def phase_grid(*, steps: Optional[Dict[str, int]] = None, seeds: int = 2,
               n_honest: int = 10, f: int = 3, kernels: str = "auto",
               expect_label: str = "pallas") -> None:
    """For each algorithm, the grid with ``--kernels <kernels>`` against
    ``--kernels jnp``: final losses agree to :data:`GRID_RTOL`, aggregation
    labels read ``expect_label``. The grid's elementwise RandK has no
    kernel, so its compression label reads ``jnp`` on both runs."""
    from repro.core import sweep
    for algo, n_steps in (steps or GRID_STEPS).items():
        args = grid_args(algo, n_steps, seeds, n_honest, f)
        t0 = time.perf_counter()
        rows = sweep.main(args + ["--kernels", kernels])
        t1 = time.perf_counter()
        ref = sweep.main(args + ["--kernels", "jnp"])
        t2 = time.perf_counter()
        log(f"grid {algo} ({n_steps} rounds) aggregation labels "
            f"({kernels}): {_labels(rows, 'kernels')}, reference: "
            f"{_labels(ref, 'kernels')}; compression labels: "
            f"{_labels(rows, 'compression_kernels')}")
        if _labels(rows, "kernels") != [expect_label]:
            raise AssertionError(f"grid aggregation resolved to "
                                 f"{_labels(rows, 'kernels')}, expected "
                                 f"[{expect_label!r}]")
        if _labels(ref, "kernels") != ["jnp"]:
            raise AssertionError("reference grid did not run the jnp rules")
        if _labels(rows + ref, "compression_kernels") != ["jnp"]:
            raise AssertionError("grid compression labels changed: "
                                 f"{_labels(rows + ref, 'compression_kernels')}")
        got, want = _by_cell(rows), _by_cell(ref)
        if got.keys() != want.keys():
            raise AssertionError("grid runs produced different cells")
        worst = 0.0
        for key, r in got.items():
            a, b = r["final_loss"], want[key]["final_loss"]
            err = abs(a - b) / max(abs(b), 1e-12)
            worst = max(worst, err)
            if not (math.isfinite(a) and err <= GRID_RTOL):
                raise AssertionError(f"grid cell {key}: final loss {a} vs "
                                     f"jnp {b} (rel {err:.2e} > "
                                     f"{GRID_RTOL})")
        log(f"grid {algo}: {len(got)} cells x seeds agree, worst rel "
            f"{worst:.2e}")
        timing(f"grid {algo}", kernels_run=t1 - t0, jnp_run=t2 - t1)


# --------------------------------------------------------------------------
# phase 2: kernel parity at benchmark widths
# --------------------------------------------------------------------------


def log_pairdist_f64(b: int, x, got, want) -> None:
    """Errors of the pairdist kernel and of its oracle against the same
    distances in f64 on the host, off and on the diagonal, relative to the
    largest distance: which side of a kernel-vs-oracle gap is off."""
    import numpy as np
    x64 = np.asarray(x, np.float64)
    sq = np.sum(x64 * x64, axis=-1)
    exact = (sq[:, :, None] + sq[:, None, :]
             - 2.0 * (x64 @ np.swapaxes(x64, 1, 2)))
    n = exact.shape[-1]
    exact[:, np.arange(n), np.arange(n)] = 0.0
    diag = np.eye(n, dtype=bool)
    scale = np.max(np.abs(exact))
    for name, out in (("kernel", got), ("oracle", want)):
        e = np.abs(np.asarray(out, np.float64) - exact) / scale
        log(f"pairdist B={b} {name} vs f64: off-diagonal "
            f"{e[:, ~diag].max():.2e}, diagonal {e[:, diag].max():.2e}")


def phase_kernels(*, d: int = 1 << 20, n: int = 13, f: int = 3,
                  batches: Sequence[int] = (1, 8), block: int = 512,
                  ratio: float = 0.05, seq: int = 2048, heads: int = 32,
                  head_dim: int = 80) -> None:
    """Each compiled kernel against its oracle; flash attention at
    stablelm_3b head shapes (head_dim 80 pads to 128 inside the op)."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.cwtm.cwtm import cwtm_pallas_batched
    from repro.kernels.cwtm.ref import cwtm_ref
    from repro.kernels.flash_attention.ops import attention
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.kernels.median.median import median_pallas_batched
    from repro.kernels.median.ref import median_ref
    from repro.kernels.pairdist.pairdist import pairdist_pallas_batched
    from repro.kernels.pairdist.ref import pairdist_ref
    from repro.kernels.randk import randk as RK
    from repro.kernels.randk import ref as RR

    def check(name, kernel, oracle, args, bound, measure=rel_err,
              diagnose=None):
        t0 = time.perf_counter()
        kern = jax.jit(kernel).lower(*args).compile()
        compile_s = time.perf_counter() - t0
        got = jax.block_until_ready(kern(*args))
        t1 = time.perf_counter()
        got = jax.block_until_ready(kern(*args))
        run_s = time.perf_counter() - t1
        want = jax.jit(oracle)(*args)
        err = measure(got, want)
        log(f"kernel {name}: err {err:.2e} (bound {bound:.0e})")
        timing(f"kernel {name}", compile=compile_s, step=run_s)
        if diagnose is not None:
            diagnose(got, want)
        if not err <= bound:
            raise AssertionError(f"kernel {name}: err {err:.2e} > {bound}")

    key = jax.random.PRNGKey(0)
    for b in batches:
        x = jax.random.normal(key, (b, n, d), jnp.float32)
        check(f"cwtm B={b}", lambda a: cwtm_pallas_batched(a, f),
              lambda a: cwtm_ref(a, f), (x,), F32_REL)
        check(f"median B={b}", median_pallas_batched, median_ref, (x,),
              F32_REL)
        check(f"pairdist B={b}", pairdist_pallas_batched, pairdist_ref,
              (x,), F32_REL, diagnose=lambda got, want, b=b, x=x:
              log_pairdist_f64(b, x, got, want))
        del x

    nb = d // block
    kb = max(1, round(ratio * nb))
    ks = jax.random.split(key, 5)
    bank = jax.random.normal(ks[0], (4, d), jnp.float32)
    m = jax.random.normal(ks[1], (d,), jnp.float32)
    ids = jnp.sort(jax.random.permutation(ks[2], nb)[:kb]).astype(jnp.int32)
    local = jnp.sort(jax.vmap(lambda kk: jax.random.permutation(kk, nb)[:kb])(
        jax.random.split(ks[4], 4)), axis=-1).astype(jnp.int32)
    alpha = 1.0 / ratio
    for mask, i in (("global", ids), ("local", local)):
        payload = RR.block_compress_ref(bank, i, block, alpha)
        check(f"randk compress {mask}",
              lambda a, i: RK.block_compress(a, i, block, alpha),
              lambda a, i: RR.block_compress_ref(a, i, block, alpha),
              (bank, i), F32_REL)
        check(f"randk decompress {mask}",
              lambda p, i: RK.block_decompress(p, i, block, d),
              lambda p, i: RR.block_decompress_ref(p, i, block, d),
              (payload, i), F32_REL)
    check("randk scatter",
          lambda r, p, i: RK.momentum_scatter(r, p, i, block, 0.9),
          lambda r, p, i: RR.momentum_scatter_ref(r, p, i, block, 0.9),
          (m, RR.block_compress_ref(bank, ids, block, alpha)[0], ids),
          F32_REL)

    q, k, v = (jax.random.normal(kk, (1, seq, heads, head_dim),
                                 jnp.float32).astype(jnp.bfloat16)
               for kk in jax.random.split(ks[3], 3))

    def max_abs(got, want):
        import numpy as np
        return float(np.max(np.abs(np.asarray(got, np.float32)
                                   - np.asarray(want, np.float32))))

    check("flash attention bf16",
          lambda a, b_, c: attention(a, b_, c, use_pallas=True),
          lambda a, b_, c: attention_ref(a.astype(jnp.float32),
                                         b_.astype(jnp.float32),
                                         c.astype(jnp.float32)),
          (q, k, v), BF16_ATOL, measure=max_abs)


# --------------------------------------------------------------------------
# phase 3 (and the four-chip flatten check): the trainer
# --------------------------------------------------------------------------


def train_spec(n_layers: int, use_pallas: Optional[bool], model=None):
    """``stablelm_3b`` (or ``model``) cut to ``n_layers``, with the
    flash-attention backend set by ``use_pallas``."""
    from repro.configs import get_arch
    spec = get_arch("stablelm_3b")
    cfg = (model or spec.model).with_overrides(
        n_layers=n_layers, use_flash_attention=use_pallas)
    return dataclasses.replace(spec, model=cfg)


def train_overrides(use_pallas: Optional[bool], *, ratio: float = 0.05,
                    f: int = 1, kind: str = "block") -> Dict:
    from repro.core import AggregatorConfig, AttackConfig, SparsifierConfig
    return {
        "name": "rosdhb", "f": f, "gamma": 1e-3,
        "momentum_dtype": "bfloat16", "server_compute_dtype": "bfloat16",
        "sparsifier": SparsifierConfig(kind=kind, ratio=ratio,
                                       block_size=512, use_pallas=use_pallas),
        "aggregator": AggregatorConfig(name="cwtm", f=f,
                                       use_pallas=use_pallas),
        "attack": AttackConfig(name="alie"),
    }


def peak_bytes(memory) -> int:
    """Device bytes a compiled step holds at once: its arguments, outputs
    and temporaries. An output aliased to a donated argument lives in that
    argument's buffer, yet the TPU compiler counts it in the temporaries
    as well as in the outputs, so aliased bytes come off twice."""
    return (memory.argument_size_in_bytes + memory.output_size_in_bytes
            + memory.temp_size_in_bytes - 2 * memory.alias_size_in_bytes)


def l2_rel(got, want) -> float:
    """||got - want|| / ||want|| over whole pytrees, in f64 on the host."""
    import jax
    import numpy as np
    num = den = 0.0
    for g, w in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        g = np.asarray(g, np.float64)
        w = np.asarray(w, np.float64)
        num += float(np.sum((g - w) ** 2))
        den += float(np.sum(w ** 2))
    return math.sqrt(num / max(den, 1e-300))


def _updates(params, params0):
    """Per-leaf ``params - params0`` on the host."""
    import jax
    import numpy as np
    return jax.tree_util.tree_map(
        lambda p, q: np.asarray(p, np.float64) - np.asarray(q, np.float64),
        jax.device_get(params), params0)


def _shift_blocks(tree, block: int = 512):
    """Every leaf's values moved by one Block-RandK block along its flat
    order: what a kernel that writes each block to the wrong slot gives."""
    import jax
    import numpy as np
    return jax.tree_util.tree_map(
        lambda x: np.roll(x.ravel(), block).reshape(x.shape), tree)


def check_bound(name: str, sound: float, control: float,
                bound: float) -> None:
    """``sound <= bound < control``: the reading passes and the broken
    control would not."""
    log(f"{name}: {sound:.3e} (bound {bound:.0e}; broken control "
        f"{control:.3e})")
    if not sound <= bound:
        raise AssertionError(f"{name} {sound:.3e} > {bound}")
    if not control > bound:
        raise AssertionError(f"{name}: the broken control {control:.3e} "
                             f"passes the bound {bound}")


def check_hidden(spec_kernel, spec_xla, params, seq_len: int) -> None:
    """Step-0 last hidden state of the bf16 model on one seeded sequence:
    flash attention against the XLA attention, within
    :data:`HIDDEN_L2_REL`. Control: the XLA model with a sliding window of
    ``seq_len / 32`` tokens — a wrong attention mask."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import transformer as tf
    half = jax.tree_util.tree_map(lambda p: p.astype(jnp.bfloat16), params)
    toks = np.random.default_rng(0).integers(
        0, spec_xla.model.vocab_size, (1, seq_len)).astype(np.int32)

    def hidden(cfg):
        fn = jax.jit(lambda p, t: tf.forward(p, cfg, {"tokens": t},
                                             mode="train")[0])
        return np.asarray(fn(half, toks).astype(jnp.float32))

    t0 = time.perf_counter()
    got = hidden(spec_kernel.model)
    t1 = time.perf_counter()
    want = hidden(spec_xla.model)
    broken = hidden(spec_xla.model.with_overrides(
        sliding_window=max(1, seq_len // 32)))
    timing("trainer hidden state", kernel=t1 - t0,
           xla_and_control=time.perf_counter() - t1)
    check_bound("trainer step-0 hidden state, flash vs xla (l2 rel)",
                l2_rel(got, want), l2_rel(broken, want), HIDDEN_L2_REL)


def phase_trainer(*, n_layers: int = TRAIN_LAYERS, seq_len: int = 2048,
                  local_batch: int = 1, steps: int = 3, n_workers: int = 4,
                  model=None, use_pallas: Optional[bool] = None,
                  expect_label: str = "pallas",
                  min_free_bytes: Optional[int] = MIN_FREE_BYTES) -> None:
    """Train ``steps`` rounds with the kernels (``use_pallas``) and with the
    XLA paths from the same parameters, masks and batches: finite losses,
    matching step-0 hidden states and losses, and matching parameter
    updates after ``steps`` rounds (:data:`UPDATE_L2_REL`; control: the
    kernel run's update shifted by one Block-RandK block)."""
    import jax
    import numpy as np
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import train
    from repro.models import model_init
    log(f"reduced: stablelm_3b layers={n_layers} (published 32) "
        f"local_batch={local_batch} seq_len={seq_len} workers={n_workers}")
    mesh = make_host_mesh()
    kernel_spec = train_spec(n_layers, use_pallas, model)
    xla_spec = train_spec(n_layers, False, model)
    # both train runs start from these parameters (seed 0)
    params0 = model_init(jax.random.PRNGKey(0), kernel_spec.model)
    check_hidden(kernel_spec, xla_spec, params0, seq_len)
    params0 = jax.device_get(params0)

    sizes = dict(n_workers=n_workers, seq_len=seq_len,
                 local_batch=local_batch, steps=steps)
    run = train(kernel_spec, mesh, algo_overrides=train_overrides(use_pallas),
                log=log, **sizes)
    timing("trainer", compile=run.compile_s, step=float(np.median(
        run.step_s[1:] or run.step_s)))
    peak = peak_bytes(run.memory)
    log(f"trainer step memory: {peak / 1e9:.3f} GB of "
        f"{HBM_BYTES / 1e9:.0f} GB ({run.memory})")
    if set(run.kernels.values()) != {expect_label}:
        raise AssertionError(f"trainer kernels {run.kernels}, expected "
                             f"{expect_label!r} throughout")
    if not all(math.isfinite(x) for x in run.losses):
        raise AssertionError(f"non-finite trainer loss: {run.losses}")
    if min_free_bytes is not None and HBM_BYTES - peak < min_free_bytes:
        raise AssertionError(f"trainer step leaves "
                             f"{(HBM_BYTES - peak) / 1e9:.2f} GB free")
    losses = run.losses
    upd = _updates(run.state.params, params0)
    del run
    ref = train(xla_spec, mesh, algo_overrides=train_overrides(False),
                log=log, **sizes)
    timing("trainer xla reference", compile=ref.compile_s,
           step=float(np.median(ref.step_s[1:] or ref.step_s)))
    if set(ref.kernels.values()) != {"jnp"}:
        raise AssertionError(f"reference kernels {ref.kernels}")
    err = abs(losses[0] - ref.losses[0]) / abs(ref.losses[0])
    log(f"trainer step-0 loss {losses[0]:.6f} vs xla {ref.losses[0]:.6f} "
        f"(rel {err:.2e}, bound {LOSS_RTOL:.0e})")
    if not err <= LOSS_RTOL:
        raise AssertionError(f"trainer step-0 loss rel {err:.2e} > "
                             f"{LOSS_RTOL}")
    want = _updates(ref.state.params, params0)
    del ref
    check_bound(f"trainer update after {steps} steps, kernels vs xla "
                f"(l2 rel)", l2_rel(upd, want),
                l2_rel(_shift_blocks(upd), want), UPDATE_L2_REL)


def fit_depth(layers: Sequence[int] = (1, 2, 3), *, seq_len: int = 2048,
              local_batch: int = 1, n_workers: int = 4) -> Dict[int, int]:
    """Peak bytes of the phase-3 train step per depth — the larger of its
    kernel and XLA variants — compiled for one chip of a described v5e (no
    chip needed). The kernel dispatch is steered to its TPU branch, as on
    the chip; run it in a process of its own."""
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh

    import repro.kernels
    from repro.configs.base import InputShape
    from repro.launch.steps import make_train_plan
    from repro.launch.train import compile_train_step
    repro.kernels.on_tpu = lambda: True
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"))
    shape = InputShape("train", seq_len, n_workers * local_batch, "train")
    out = {}
    for n_layers in layers:
        for use_pallas in (None, False):
            plan = make_train_plan(train_spec(n_layers, use_pallas), shape,
                                   mesh, train_overrides(use_pallas),
                                   n_workers=n_workers)
            peak = peak_bytes(compile_train_step(plan, mesh)
                              .memory_analysis())
            out[n_layers] = max(out.get(n_layers, 0), peak)
            log(f"fit: layers={n_layers} use_pallas={use_pallas} "
                f"peak {peak / 1e9:.3f} GB")
    return out


def phase_flatten(*, n_layers: int = TRAIN_LAYERS, model=None,
                  seq_len: int = 2048, local_batch: int = 1, steps: int = 2,
                  use_pallas: Optional[bool] = None,
                  expect_agg: str = "pallas") -> None:
    """The trainer on a ``(4, 1)`` mesh with n_workers = 4, at phase 3's
    widths and depth: the sharded bank flatten (the all-to-all) against the
    same round on one of the four devices, then the sharded flatten with
    phase 3's Block-RandK.

    The reference is phase 3's one-device program (naive flatten, no
    exchange), with the attention backend the four-device plan resolved
    to. The ``flatten="naive"`` GSPMD layout is not the reference: at
    these widths its four-chip compile outgrew 39 GB of host memory. The
    comparison runs uncompressed: a mask is drawn over bank coordinates,
    whose order the layouts permute. Parameter updates after ``steps``
    rounds agree within :data:`FLATTEN_L2_REL` (control: the sharded
    update shifted by one block). The compressed run must give finite
    losses and an update that differs from the uncompressed one.
    Aggregation runs its kernel device-local on each chip's coordinate
    slice (``expect_agg``)."""
    import jax
    import numpy as np
    from jax.sharding import AxisType
    from repro.launch.mesh import make_host_mesh
    from repro.launch.train import train
    from repro.models import model_init
    mesh = make_host_mesh()
    if dict(mesh.shape) != {"data": 4, "model": 1}:
        raise AssertionError(f"four-device mesh expected, got {mesh.shape}")
    one = jax.sharding.Mesh(np.array(jax.devices()[:1]).reshape(1, 1),
                            ("data", "model"),
                            axis_types=(AxisType.Auto,) * 2)
    spec = train_spec(n_layers, use_pallas, model)
    log(f"reduced: stablelm_3b layers={n_layers} (published 32) "
        f"local_batch={local_batch} seq_len={seq_len} workers=4")
    params0 = jax.device_get(model_init(jax.random.PRNGKey(0), spec.model))
    kw = dict(n_workers=4, seq_len=seq_len, local_batch=local_batch,
              steps=steps, log=log)
    plain = train_overrides(use_pallas, kind="none", ratio=1.0)
    runs = {}

    def run(name, on, flatten, arch, overrides):
        r = train(arch, on, flatten=flatten, algo_overrides=overrides, **kw)
        log(f"flatten {name}: kernels {r.kernels}; step memory "
            f"{peak_bytes(r.memory) / 1e9:.3f} GB per chip")
        if r.plan.flatten != flatten:
            raise AssertionError(f"plan fell back to {r.plan.flatten}")
        if r.kernels["aggregation"] != expect_agg:
            raise AssertionError(f"aggregation ran {r.kernels}")
        if not all(math.isfinite(x) for x in r.losses):
            raise AssertionError(f"non-finite loss: {r.losses}")
        timing(f"flatten {name}", compile=r.compile_s, step=r.step_s[-1])
        runs[name] = (_updates(r.state.params, params0), r.losses)
        return r.plan

    plan = run("sharded", mesh, "sharded", spec, plain)
    run("one device", one, "naive",
        dataclasses.replace(spec, model=plan.model), plain)
    run("sharded block", mesh, "sharded", spec,
        train_overrides(use_pallas))
    (u_s, l_s), (u_1, l_1) = runs["sharded"], runs["one device"]
    log(f"flatten losses: sharded {l_s}, one device {l_1}")
    check_bound(f"four-chip sharded vs one-device update after {steps} "
                f"steps (l2 rel)", l2_rel(u_s, u_1),
                l2_rel(_shift_blocks(u_s), u_1), FLATTEN_L2_REL)
    u_b, l_b = runs["sharded block"]
    moved = l2_rel(u_b, u_s)
    log(f"flatten sharded + Block-RandK: losses {l_b}; update vs "
        f"uncompressed l2 rel {moved:.3e}")
    if not 0.0 < moved < math.inf:
        raise AssertionError("Block-RandK left the update unchanged")


def phase_grid_shard(*, steps: int = GRID_STEPS["rosdhb"], seeds: int = 2,
                     n_honest: int = 10, f: int = 3) -> None:
    """The MNIST grid laid over every device (``--shard``) against one
    device (``--no-shard``): identical rows."""
    from repro.core import sweep
    args = grid_args("rosdhb,dasha", steps, seeds, n_honest, f)
    t0 = time.perf_counter()
    sharded = sweep.main(args + ["--shard"])
    t1 = time.perf_counter()
    single = sweep.main(args + ["--no-shard"])
    t2 = time.perf_counter()
    a, b = _by_cell(sharded), _by_cell(single)
    if a != b:
        diff = [k for k in a if a[k] != b.get(k)]
        raise AssertionError(f"sharded grid rows differ at {diff[:4]}")
    log(f"grid --shard vs --no-shard: {len(a)} identical rows")
    timing("grid shard", sharded=t1 - t0, single=t2 - t1)


# --------------------------------------------------------------------------
# phase 4: the parameter server
# --------------------------------------------------------------------------


def phase_server(*, rounds: int = 5, expect_label: str = "pallas",
                 extra: Sequence[str] = ()) -> None:
    from repro.serve.__main__ import main as serve_main
    t0 = time.perf_counter()
    summary = serve_main(["--scenario", "stateless-linear",
                          "--cell", "rosdhb/foe/median",
                          "--rounds", str(rounds), *extra])
    timing("server", run=time.perf_counter() - t0)
    if summary["step_traces"] != 1:
        raise AssertionError(f"server traced {summary['step_traces']} times")
    if summary["kernels"] != expect_label:
        raise AssertionError(f"server kernels {summary['kernels']}")
    if not math.isfinite(summary["final_honest_loss"]):
        raise AssertionError("non-finite server loss")


# --------------------------------------------------------------------------


def main(argv: Optional[Sequence[str]] = None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--chips", type=int, default=1, choices=(1, 4),
                   help="4: run only the paths that exist across chips")
    args = p.parse_args(argv)

    sys.path.insert(0, os.path.join(REPO, "src"))
    import jax
    from repro.utils.compile_cache import enable_compile_cache

    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {platform!r}); nothing run",
              file=sys.stderr)
        return 2
    if len(devices) != args.chips:
        print(f"chip_smoke: --chips {args.chips} but {len(devices)} TPU "
              f"devices", file=sys.stderr)
        return 2
    log(f"cache: {enable_compile_cache()}")
    log(f"device: {platform} {devices[0].device_kind} x{len(devices)}")

    if args.chips == 4:
        phases = [("flatten", phase_flatten), ("grid shard", phase_grid_shard)]
    else:
        phases = [("grid", phase_grid), ("kernels", phase_kernels),
                  ("trainer", phase_trainer), ("server", phase_server)]
    failed = []
    for name, phase in phases:
        t0 = time.perf_counter()
        try:
            phase()
        except Exception:  # report every phase, then fail
            traceback.print_exc()
            failed.append(name)
            log(f"phase {name}: FAILED")
            continue
        timing(f"phase {name} total", wall=time.perf_counter() - t0)
        log(f"phase {name}: passed")
    if failed:
        print(f"chip_smoke: phases failed: {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
