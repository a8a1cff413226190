"""Training launcher: the robust compressed round on a sharded model.

Builds its mesh over the devices present (``make_host_mesh``: one chip, a
four-chip host, or virtual CPU devices) and runs the named architecture at
its published widths. Depth, worker count, sequence length and local batch
come from flags; nothing here depends on the backend. The 256-chip
production configurations are compile-proven by ``repro.launch.dryrun``.

    PYTHONPATH=src python -m repro.launch.train --arch stablelm_3b \
        --layers 2 --workers 4 --f 1 --seq-len 2048 --local-batch 1 \
        --steps 3 --ratio 0.05
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from typing import Any, Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import checkpoint as ckpt
from repro.configs import get_arch
from repro.configs.base import ArchSpec, InputShape
from repro.core import AggregatorConfig, AttackConfig, SparsifierConfig
from repro.core import aggregators as agg_lib
from repro.core import algorithms as alg
from repro.core import stages
from repro.data import ChunkPrefetcher
from repro.kernels import backend_label, resolve_kernel_backend
from repro.launch.mesh import make_host_mesh
from repro.launch.steps import (TrainPlan, TrainState,
                                build_chunked_train_step, build_train_step,
                                make_train_plan, stream_batch_specs,
                                train_input_specs)
from repro.models import model_init
from repro.utils.compile_cache import enable_compile_cache


@dataclasses.dataclass
class TrainRun:
    """What :func:`train` did: per-step losses, the final state, the resolved
    kernel backends, the compiled step's memory analysis and its times."""

    plan: TrainPlan
    losses: List[float]
    state: TrainState
    kernels: Dict[str, str]
    memory: Any
    compile_s: float
    step_s: List[float]


def kernel_labels(plan: TrainPlan) -> Dict[str, str]:
    """Backend each kernelised stage of the step resolves to (``pallas`` |
    ``pallas-interpret`` | ``jnp``) — compression reports ``jnp`` for a
    config its kernels cannot take, so the label is what runs."""
    return {
        "aggregation": agg_lib.rule_backend_label(plan.algo.aggregator),
        "compression": alg.compression_backend_label(
            plan.algo, plan.flat_spec.padded_size),
        "attention": backend_label(resolve_kernel_backend(
            plan.model.use_flash_attention)),
    }


def synthetic_batch(plan: TrainPlan, gen: np.random.Generator
                    ) -> Dict[str, np.ndarray]:
    """One round's ``[n_workers, local_batch, ...]`` batch from ``gen``:
    token streams with a learnable odd-position successor rule."""
    cfg = plan.model
    n, lb, s = plan.n_workers, plan.local_batch, plan.shape.seq_len
    toks = gen.integers(0, cfg.vocab_size, (n, lb, s))
    toks[..., 1::2] = (toks[..., 0::2] + 1) % cfg.vocab_size
    batch = {"tokens": np.asarray(toks, np.int32)}
    if cfg.input_kind != "tokens":
        batch = {
            "embeddings": np.asarray(gen.normal(size=(n, lb, s, cfg.d_model)),
                                     np.float32),
            "targets": np.asarray(toks % cfg.vocab_size, np.int32),
        }
    if cfg.family == "vlm":
        batch["image_embeddings"] = np.asarray(
            gen.normal(size=(n, lb, cfg.n_image_tokens, cfg.d_model)),
            np.float32)
    return batch


def compile_train_step(plan: TrainPlan, mesh: Mesh,
                       chunk_size: Optional[int] = None):
    """Compile the train step ahead of time for the plan's input specs on
    ``mesh`` — with ``chunk_size``, the step scanned over that many rounds
    (:func:`build_chunked_train_step`). The state is donated: the ``[n, D]``
    bank and the master params are the largest buffers, and without
    donation each would exist twice at the step's peak. The executable is
    kept as the step compiled last (``repro.core.stages.record``), whose
    stage table is built only when read."""
    state_specs, batch_specs = train_input_specs(plan, mesh)
    step = build_train_step(plan, mesh)
    if chunk_size is not None:
        step = build_chunked_train_step(plan, mesh, chunk_size)
        batch_specs = stream_batch_specs(plan, mesh, chunk_size)
    # the state leaves the step laid out as it entered, so the next step
    # takes it as is
    state_sh = jax.tree_util.tree_map(
        lambda s: s.sharding or NamedSharding(mesh, P()), state_specs,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    with mesh:
        compiled = jax.jit(step, donate_argnums=0,
                           out_shardings=(state_sh, None)).lower(
            state_specs, batch_specs).compile()
    stages.record(compiled)
    return compiled


def init_train_state(plan: TrainPlan, compiled, seed: int) -> TrainState:
    """The round-0 state (params from ``seed``, round keys from
    ``seed + 1``) placed with ``compiled``'s own input shardings."""
    state_sh = compiled.input_shardings[0][0]
    return jax.device_put(TrainState(
        params=model_init(jax.random.PRNGKey(seed), plan.model),
        server=alg.init_state(plan.algo, plan.flat_spec.padded_size),
        step=jnp.zeros((), jnp.int32),
        key=jax.random.PRNGKey(seed + 1)), state_sh)


def _plan(spec: ArchSpec, mesh: Mesh, n_workers: int, seq_len: int,
          local_batch: int, algo_overrides: Optional[Dict], flatten: str,
          log, note: str = "") -> TrainPlan:
    shape = InputShape("train", seq_len, n_workers * local_batch, "train")
    plan = make_train_plan(spec, shape, mesh, algo_overrides,
                           n_workers=n_workers, flatten=flatten)
    log(f"[train] {plan.model.name} L={plan.model.n_layers} "
        f"D={plan.flat_spec.padded_size:,} n_workers={plan.n_workers} "
        f"f={plan.algo.f} algo={plan.algo.name} "
        f"k/d={plan.algo.sparsifier.ratio} flatten={plan.flatten} "
        f"mesh={dict(mesh.shape)}{note} kernels={kernel_labels(plan)}")
    return plan


def train(spec: ArchSpec, mesh: Mesh, *, n_workers: int, seq_len: int,
          local_batch: int, steps: int,
          algo_overrides: Optional[Dict] = None, flatten: str = "sharded",
          seed: int = 0, log=print) -> TrainRun:
    """Compile the train step for ``mesh`` ahead of time and run ``steps``
    rounds on seeded synthetic tokens; parameters and server state are
    placed with the step's own input shardings and donated to it."""
    plan = _plan(spec, mesh, n_workers, seq_len, local_batch,
                 algo_overrides, flatten, log)
    t0 = time.perf_counter()
    compiled = compile_train_step(plan, mesh)
    compile_s = time.perf_counter() - t0
    with mesh:
        state = init_train_state(plan, compiled, seed)
        batch_sh = compiled.input_shardings[0][1]
        rng = np.random.default_rng(seed)
        losses, step_s = [], []
        for t in range(steps):
            batch = jax.device_put(synthetic_batch(plan, rng), batch_sh)
            t0 = time.perf_counter()
            state, metrics = compiled(state, batch)
            loss = float(metrics["loss"])  # waits for the step
            step_s.append(time.perf_counter() - t0)
            losses.append(loss)
            log(f"[train] step {t:4d} loss={loss:.4f} "
                f"|R|={float(metrics['dir_norm']):.3f} "
                f"({step_s[-1]:.3f}s)")
    return TrainRun(plan, losses, state, kernel_labels(plan),
                    compiled.memory_analysis(), compile_s, step_s)


def train_streaming(spec: ArchSpec, mesh: Mesh, *, n_workers: int,
                    seq_len: int, local_batch: int, steps: int,
                    chunk_size: int, prefetch_depth: int,
                    algo_overrides: Optional[Dict] = None, seed: int = 0,
                    log=print) -> TrainState:
    """Feed the rounds through the prefetched ring buffer
    (``repro.data.stream``), scanning ``chunk_size`` rounds per dispatch —
    O(prefetch_depth) host residency. The chunked step is compiled and
    donated as :func:`train`'s step is."""
    plan = _plan(spec, mesh, n_workers, seq_len, local_batch,
                 algo_overrides, "sharded", log,
                 note=f" stream chunk={chunk_size} depth={prefetch_depth}")
    chunk_step = compile_train_step(plan, mesh, chunk_size)
    # pure-fn-of-t schedule so the prefetch thread owns its RNG
    batch_fn = lambda t: synthetic_batch(  # noqa: E731
        plan, np.random.default_rng((seed, t)))
    with mesh:
        state = init_train_state(plan, chunk_step, seed)
        chunk_sh = chunk_step.input_shardings[0][1]
        t0, t = time.time(), 0
        with ChunkPrefetcher(batch_fn, steps, chunk_size,
                             prefetch_depth) as pf:
            while True:
                chunks = pf.take(1)
                if not chunks:
                    break
                state, metrics = chunk_step(
                    state, jax.device_put(chunks[0], chunk_sh))
                t += chunk_size
                log(f"[train] step {t:4d} "
                    f"loss={float(metrics['loss'][-1]):.4f}"
                    f" |R|={float(metrics['dir_norm'][-1]):.3f}"
                    f" ({time.time()-t0:.1f}s)")
            log(f"[train] host high-water: {pf.high_water_bytes:,} B "
                f"({pf.high_water_chunks} chunks)")
        if steps % chunk_size:
            # remainder rounds, one dispatch each
            step = compile_train_step(plan, mesh)
            batch_sh = step.input_shardings[0][1]
            for t in range(steps - steps % chunk_size, steps):
                state, metrics = step(
                    state, jax.device_put(batch_fn(t), batch_sh))
    return state


def main(argv: Optional[Sequence[str]] = None) -> TrainState:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--layers", type=int, default=None,
                   help="model depth (default: the published depth); the "
                        "only width-preserving cut")
    p.add_argument("--workers", type=int, default=4,
                   help="RoSDHB workers; when it equals the mesh's data "
                        "extent, the bank exchange is the sharded "
                        "all-to-all")
    p.add_argument("--seq-len", type=int, default=4096)
    p.add_argument("--local-batch", type=int, default=1,
                   help="sequences per worker per round")
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--algo", default="rosdhb",
                   choices=["rosdhb", "dasha", "robust_dgd", "dgd"])
    p.add_argument("--ratio", type=float, default=0.05)
    p.add_argument("--f", type=int, default=None)
    p.add_argument("--attack", default="alie")
    p.add_argument("--gamma", type=float, default=1e-3)
    p.add_argument("--local-masks", action="store_true")
    p.add_argument("--momentum-dtype", default="float32")
    p.add_argument("--checkpoint", default=None)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--stream", action="store_true",
                   help="feed batches through the prefetched ring buffer "
                        "(repro.data.stream) and scan --chunk-size rounds "
                        "per dispatch — O(prefetch_depth) host residency")
    p.add_argument("--chunk-size", type=int, default=8)
    p.add_argument("--prefetch-depth", type=int, default=2)
    args = p.parse_args(argv)
    enable_compile_cache()

    spec = get_arch(args.arch)
    if args.layers is not None:
        spec = dataclasses.replace(spec, model=spec.model.with_overrides(
            n_layers=args.layers))
    overrides = {
        "name": args.algo, "gamma": args.gamma,
        "momentum_dtype": args.momentum_dtype,
        "sparsifier": SparsifierConfig(
            kind="block", ratio=args.ratio, block_size=512,
            local=args.local_masks),
        "attack": AttackConfig(name=args.attack),
    }
    if args.f is not None:
        overrides["f"] = args.f
        overrides["aggregator"] = AggregatorConfig(name="cwtm",
                                                   f=max(args.f, 1))
    sizes = dict(n_workers=args.workers, seq_len=args.seq_len,
                 local_batch=args.local_batch, steps=args.steps,
                 algo_overrides=overrides, seed=args.seed)
    mesh = make_host_mesh()
    if args.stream:
        state = train_streaming(spec, mesh, chunk_size=args.chunk_size,
                                prefetch_depth=args.prefetch_depth, **sizes)
    else:
        state = train(spec, mesh, **sizes).state
    if args.checkpoint:
        ckpt.save(args.checkpoint, {"params": state.params},
                  step=args.steps)
        print(f"[train] checkpoint -> {args.checkpoint}")
    return state


if __name__ == "__main__":
    main()
