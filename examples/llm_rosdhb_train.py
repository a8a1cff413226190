"""LLM-scale RoSDHB path on the host mesh: trains a reduced qwen-family
transformer (~3M params) for a few hundred steps with the SAME pjit train
step used by the production dry-run — per-worker vmapped gradients,
coordinate-sharded momentum bank, Byzantine overwrite, CWTM.

    PYTHONPATH=src python examples/llm_rosdhb_train.py --steps 60
"""

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import INPUT_SHAPES, get_arch
from repro.configs.base import ArchSpec, InputShape
from repro.core import (AggregatorConfig, AttackConfig, SparsifierConfig)
from repro.core import algorithms as alg
from repro.core import compression as C
from repro.launch import make_host_mesh
from repro.launch.steps import (TrainState, build_train_step,
                                make_train_plan)
from repro.models import model_init
from repro.utils import tree as T


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="qwen25_3b")
    p.add_argument("--steps", type=int, default=60)
    p.add_argument("--n-workers", type=int, default=8)
    p.add_argument("--f", type=int, default=2)
    p.add_argument("--ratio", type=float, default=0.1)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--batch", type=int, default=16)
    args = p.parse_args()

    spec = get_arch(args.arch)
    reduced = ArchSpec(model=spec.model.reduced(n_layers=2, d_model=256)
                       .with_overrides(vocab_size=512),
                       citation=spec.citation)
    shape = InputShape("host_train", args.seq, args.batch, "train")
    mesh = make_host_mesh()

    plan = make_train_plan(
        reduced, shape, mesh, n_workers=args.n_workers,
        algo_overrides={
            "f": args.f, "gamma": 0.5,
            "sparsifier": SparsifierConfig(kind="block", ratio=args.ratio,
                                           block_size=128),
            "aggregator": AggregatorConfig(name="cwtm", f=args.f),
            "attack": AttackConfig(name="alie"),
            "momentum_dtype": "float32",
        })
    step = jax.jit(build_train_step(plan, mesh))
    cfg = plan.model

    with mesh:
        params = model_init(jax.random.PRNGKey(0), cfg)
        state = TrainState(
            params=params,
            server=alg.init_state(plan.algo, plan.flat_spec.padded_size),
            step=jnp.zeros((), jnp.int32),
            key=jax.random.PRNGKey(1))

        rng = np.random.default_rng(0)
        lb = shape.global_batch // plan.n_workers
        d = plan.flat_spec.padded_size
        print(f"arch={args.arch}(reduced) d={d} params, "
              f"n_workers={plan.n_workers} f={args.f} k/d={args.ratio} "
              f"uplink={C.payload_floats(d, plan.algo.sparsifier)} "
              f"floats/worker")
        t0 = time.time()
        for t in range(args.steps):
            toks = rng.integers(0, cfg.vocab_size,
                                (plan.n_workers, lb, args.seq))
            toks[..., 1::2] = (toks[..., 0::2] + 1) % cfg.vocab_size
            batch = {"tokens": jnp.asarray(toks, jnp.int32)}
            state, metrics = step(state, batch)
            if t % 10 == 0 or t == args.steps - 1:
                print(f"step {t:4d} loss={float(metrics['loss']):.4f} "
                      f"|R|={float(metrics['dir_norm']):.3f} "
                      f"({time.time()-t0:.1f}s)")
        assert float(metrics["loss"]) < 6.1
        print("OK: loss decreasing under ALIE with 10x-compressed uplink.")


if __name__ == "__main__":
    main()
