"""pjit train/serve step builders for the production mesh.

``build_train_step`` wires the paper's algorithm into the sharded model:

  1. per-worker gradients via ``vmap(grad)`` over the stacked worker axis
     (worker axis sharded over the data-parallel mesh axes — each data row
     computes exactly its own worker's gradient, tensor-sharded over
     ``model``);
  2. gradients are flattened to the coordinate-sharded server layout
     ``[n_workers, D]`` with ``D`` sharded over ALL mesh axes — GSPMD lowers
     the resharding to the all-to-all that realises "workers send compressed
     coordinates to the (virtual) server";
  3. ``core.algorithms.server_round`` runs the paper's steps 1-6 (masks,
     unbiased reconstruction, Byzantine overwrite, per-worker momentum,
     robust aggregation) locally per coordinate slice;
  4. the aggregate is unflattened back to the parameter layout (step 7).

``build_serve_step`` is the standard sharded forward (prefill or single-token
decode with KV/SSM caches) — RoSDHB is a training-time mechanism.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.configs.base import ArchSpec, InputShape, model_for_shape
from repro.core import algorithms as alg
from repro.core import aggregators as agg_lib
from repro.core import attacks as atk_lib
from repro.core import compression as comp_lib
from repro.core import stages
from repro.kernels import resolve_kernel_backend
from repro.models import transformer as tf
from repro.models.config import ModelConfig
from repro.sharding import partitioning as sp
from repro.sharding import flatten as sf
from repro.utils import tree as T


class TrainState(NamedTuple):
    params: Any            # model parameter pytree (f32 master)
    server: alg.ServerState  # RoSDHB bank [n_workers, Dp] etc.
    step: jnp.ndarray
    key: jax.Array


@dataclasses.dataclass(frozen=True)
class TrainPlan:
    """Everything the launcher/dry-run needs to build + shard a train step.

    ``flatten``: 'sharded' (default — transpose-major, GSPMD-clean; §Perf
    iter 2) or 'naive' (reshape+concat; kept for the paper-faithful baseline
    ablation — it replicates at scale).
    """

    arch: ArchSpec
    shape: InputShape
    model: ModelConfig
    algo: alg.AlgorithmConfig
    flat_spec: Any
    n_workers: int
    local_batch: int
    flatten: str = "sharded"


def _abstract_params(cfg: ModelConfig):
    # close over cfg: it is a plain dataclass, not a pytree
    return jax.eval_shape(lambda: tf.model_init(jax.random.PRNGKey(0), cfg))


def _spmd_flag(flag: Optional[bool], stage: str) -> Optional[bool]:
    """A stage's ``use_pallas`` on a multi-device mesh, where the SPMD
    partitioner cannot split a compiled Pallas (Mosaic) kernel: left to the
    backend (None) it becomes the XLA path; forced onto the compiled kernel
    it is refused. Interpret mode lowers to plain HLO and stands."""
    if flag is None:
        return False
    kb = resolve_kernel_backend(flag)
    if kb is not None and not kb["interpret"]:
        raise ValueError(
            f"{stage}: a compiled Pallas kernel cannot be partitioned "
            f"across a multi-device mesh; leave use_pallas=None (XLA path) "
            f"or run on one device")
    return flag


def _multi_device_backends(cfg: ModelConfig, algo: alg.AlgorithmConfig):
    """Kernel choices of a plan over several devices. A coordinate-wise
    aggregator keeps its kernel (:func:`build_train_step` maps it over the
    bank's coordinate shards); attention, the Block-RandK round trip and
    row-mixing aggregators resolve per :func:`_spmd_flag`."""
    cfg = cfg.with_overrides(use_flash_attention=_spmd_flag(
        cfg.use_flash_attention, "flash attention"))
    sparsifier = dataclasses.replace(algo.sparsifier, use_pallas=_spmd_flag(
        algo.sparsifier.use_pallas, "Block-RandK"))
    aggregator = algo.aggregator
    if not agg_lib.coordinatewise(aggregator):
        aggregator = dataclasses.replace(aggregator, use_pallas=_spmd_flag(
            aggregator.use_pallas, f"aggregator {aggregator.name}"))
    return cfg, dataclasses.replace(algo, sparsifier=sparsifier,
                                    aggregator=aggregator)


def _device_local_aggregator(algo: alg.AlgorithmConfig, mesh: Mesh
                             ) -> Optional[agg_lib.Aggregator]:
    """On a multi-device mesh, the coordinate-wise aggregator applied by
    each device to its own coordinate slice of the ``[n, D]`` bank (inside
    ``shard_map``, so its Pallas kernel never meets the partitioner);
    ``None`` where the rule built from the config applies."""
    if mesh.size == 1 or not agg_lib.coordinatewise(algo.aggregator):
        return None
    axes = sp.server_axes(mesh)
    return jax.shard_map(agg_lib.make_aggregator(algo.aggregator),
                         mesh=mesh, in_specs=P(None, axes),
                         out_specs=P(axes), check_vma=False)


def make_train_plan(spec: ArchSpec, shape: InputShape, mesh: Mesh,
                    algo_overrides: Optional[Dict] = None,
                    n_workers: Optional[int] = None,
                    flatten: str = "sharded") -> TrainPlan:
    cfg = model_for_shape(spec, shape)
    n = n_workers if n_workers is not None else sp.n_workers(mesh)
    if shape.global_batch % n:
        raise ValueError(f"global_batch {shape.global_batch} not divisible "
                         f"by n_workers {n}")
    local_batch = shape.global_batch // n
    abstract = _abstract_params(cfg)
    n_chips = int(np.prod(list(mesh.shape.values())))

    ov = dict(algo_overrides or {})
    algo = alg.AlgorithmConfig(
        name=ov.pop("name", "rosdhb"),
        n_workers=n,
        f=ov.pop("f", max(1, n // 8)),
        gamma=ov.pop("gamma", 1e-3),
        beta=ov.pop("beta", 0.9),
        sparsifier=ov.pop("sparsifier", comp_lib.SparsifierConfig(
            kind="block_hash", ratio=spec.rosdhb_ratio, block_size=512)),
        aggregator=ov.pop("aggregator", agg_lib.AggregatorConfig(
            name="cwtm", f=max(1, n // 8))),
        attack=ov.pop("attack", atk_lib.AttackConfig(name="alie")),
        momentum_dtype=ov.pop("momentum_dtype", "bfloat16"),
        **ov,
    )
    if n_chips > 1:
        cfg, algo = _multi_device_backends(cfg, algo)
    # the Block-RandK kernels need whole blocks: pad D (and, sharded, every
    # chip's slice of it) to a multiple of the block
    align = 8
    if algo.sparsifier.kind == "block":
        align = math.lcm(align, algo.sparsifier.block_size)

    if n != sp.n_workers(mesh):
        # host/simulator mode: the worker axis does not match the mesh's
        # data-parallel extent, so the shard_map bank transforms do not
        # apply — use the naive flatten (fine off-mesh).
        flatten = "naive"
    if flatten == "sharded":
        flat_spec = sf.make_sharded_flat_spec(abstract, mesh,
                                              fsdp=spec.fsdp, align=align)
    else:
        flat_spec = T.make_flat_spec(abstract, pad_to=n_chips * align)
    return TrainPlan(spec, shape, cfg, algo, flat_spec, n, local_batch,
                     flatten)


def build_train_step(plan: TrainPlan, mesh: Mesh):
    """Returns (step_fn, in_shardings-compatible abstract inputs builder)."""
    cfg = plan.model
    fspec = plan.flat_spec
    algo = plan.algo
    bank_sharding = P(None, sp.server_axes(mesh))
    wire_dtype = jnp.dtype(algo.momentum_dtype)
    aggregator = _device_local_aggregator(algo, mesh)

    def loss_fn(params, batch):
        return tf.lm_loss(params, cfg, batch)

    def train_step(state: TrainState, batch: Dict[str, jnp.ndarray]
                   ) -> Tuple[TrainState, Dict[str, jnp.ndarray]]:
        # each stage runs in its named scope (repro.core.stages); the
        # server round's stages are scoped where the round is written
        with stages.scope("update"):
            key, round_key = jax.random.split(state.key)

        # (1) per-worker gradients: batch leaves are [n_workers, local, ...].
        # spmd_axis_name pins the vmapped worker dim to the data-parallel
        # mesh axes for every internal intermediate — without it the
        # per-layer saved activations inside the scan are REPLICATED over
        # the worker dim (§Perf iter 5: 283 GiB/chip of f32 residuals at
        # mistral-123B scale).
        dp = sp.dp_axes(mesh)
        with stages.scope("grads"):
            # mixed precision (§Perf iter 8): differentiate wrt a bf16 cast
            # of the f32 master params — halves the per-worker gradient
            # transient (the f32 master is only touched by the final
            # update).
            half = jax.tree_util.tree_map(
                lambda p: p.astype(jnp.bfloat16)
                if p.dtype == jnp.float32 else p, state.params)
            losses, grads = jax.vmap(
                jax.value_and_grad(loss_fn), in_axes=(None, 0),
                spmd_axis_name=dp if len(dp) > 1 else dp[0])(
                    half, batch)

            # The barrier keeps the TPU compiler from fusing the gradient
            # producers (the LM head's dot, the embedding's scatter-add)
            # into the flatten: fused, a published-width step at a 50k
            # vocabulary took it over ten minutes and ~15 GB of host
            # memory to compile.
            grads = jax.lax.optimization_barrier(grads)

        # (2) flatten to the coordinate-sharded virtual-server layout.
        # 'sharded': transpose-major flatten keeps GSPMD shardings intact in
        # the producer layout [n(data), D(model)]; the reshard to the bank
        # layout [n, D(all axes)] below is the algorithm's one all-to-all
        # ("workers send their k coordinates to the server").
        with stages.scope("flatten"):
            if plan.flatten == "sharded":
                # hand-scheduled per-leaf all-to-all into the interleaved
                # bank layout (§Perf iter 4c) — the only formulation GSPMD
                # partitions
                gflat = sf.flatten_to_bank(grads, fspec, mesh,
                                           dtype=wire_dtype)
            else:
                gflat = T.stacked_ravel(grads, fspec, dtype=wire_dtype)
                gflat = jax.lax.with_sharding_constraint(
                    gflat, NamedSharding(mesh, bank_sharding))

        # (3) paper steps 1-6 on the [n, D] bank
        direction, server, _ = alg.server_round(
            algo, state.server, gflat, round_key, aggregator=aggregator)

        # (4) step 7: unflatten + SGD update of the master params
        with stages.scope("update"):
            if plan.flatten == "sharded":
                dir_tree = sf.bank_to_param_tree(direction, fspec, mesh)
            else:
                dir_tree = T.tree_unravel(direction, fspec)
            new_params = jax.tree_util.tree_map(
                lambda p, d: (p - algo.gamma * d.astype(p.dtype)),
                state.params, dir_tree)
            metrics = {
                "loss": jnp.mean(losses[algo.f:]),
                "dir_norm": jnp.linalg.norm(direction),
            }
            step = state.step + 1
        return TrainState(new_params, server, step, key), metrics

    return train_step


def build_chunked_train_step(plan: TrainPlan, mesh: Mesh,
                             chunk_size: int):
    """Loop ``chunk_size`` rounds of :func:`build_train_step` inside ONE
    compiled program: ``chunk_step(state, chunk) -> (state, metrics)`` with
    ``chunk`` leaves ``[chunk_size, n_workers, ...]`` and metrics stacked
    ``[chunk_size]``.

    This is the device program the streaming launch driver dispatches once
    per ring-buffer chunk (``repro.data.stream.ChunkPrefetcher``): host
    dispatch and batch residency drop from O(steps) to O(chunk), and the
    loop carries exactly the ``TrainState`` of :func:`train_input_specs` —
    mirror/prev_grad slots pruned by the algorithm's resolved
    ``StateLayout``, so chunking never widens the carry.
    """
    if chunk_size <= 0:
        raise ValueError(f"chunk_size must be positive, got {chunk_size}")
    step = build_train_step(plan, mesh)

    def chunk_step(state: TrainState, chunk: Dict[str, jnp.ndarray]
                   ) -> Tuple[TrainState, Dict[str, jnp.ndarray]]:
        # a scan written out, so that the loop's own work (the round's
        # batch, the outputs' rows, the counter) runs in stage scopes too
        def cond(carry):
            with stages.scope("update"):
                return carry[0] < chunk_size

        def body(carry):
            t, state, out = carry
            with stages.scope("grads"):
                batch = jax.tree_util.tree_map(
                    lambda x: jax.lax.dynamic_index_in_dim(
                        x, t, keepdims=False), chunk)
            state, metrics = step(state, batch)
            with stages.scope("update"):
                out = jax.tree_util.tree_map(
                    lambda o, m: jax.lax.dynamic_update_index_in_dim(
                        o, m, t, 0), out, metrics)
                return t + 1, state, out

        with stages.scope("update"):
            first = jax.tree_util.tree_map(lambda x: x[0], chunk)
            out = jax.tree_util.tree_map(
                lambda m: jnp.zeros((chunk_size,) + m.shape, m.dtype),
                jax.eval_shape(step, state, first)[1])
            start = jnp.zeros((), jnp.int32)
        _, state, out = jax.lax.while_loop(cond, body, (start, state, out))
        return state, out

    return chunk_step


# --------------------------------------------------------------------------
# abstract inputs (ShapeDtypeStruct) for lower()/compile() — no allocation
# --------------------------------------------------------------------------


def _sds(shape, dtype, mesh, pspec):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, pspec))


def _attack_state_specs(algo: alg.AlgorithmConfig, d: int, mesh: Mesh):
    """Abstract ``repro.adversary.AttackState`` matching ``alg.init_state``:
    the ``[d]`` memory slots shard over the server (coordinate) axes like
    the momentum bank; ``None`` for stateless attacks (the shared
    ``needs_attack_state`` predicate keeps this locked to the real state)."""
    from repro.adversary import core as adv
    if not adv.needs_attack_state(algo.attack.name, algo.f):
        return None
    vec = _sds((d,), jnp.float32, mesh, P(sp.server_axes(mesh)))
    return adv.AttackState(
        vec=vec, mu=vec,
        scalars=_sds((adv.NUM_SCALARS,), jnp.float32, mesh, P(None)),
        step=jax.ShapeDtypeStruct((), jnp.int32))


def train_input_specs(plan: TrainPlan, mesh: Mesh):
    """(state, batch) ShapeDtypeStructs for ``jit(train_step).lower``."""
    cfg = plan.model
    abstract = _abstract_params(cfg)
    pspecs = sp.param_specs(abstract, mesh, fsdp=plan.arch.fsdp)
    params = jax.tree_util.tree_map(
        lambda a, s: _sds(a.shape, a.dtype, mesh, s), abstract, pspecs,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))

    n, d = plan.n_workers, plan.flat_spec.padded_size
    mdt = jnp.dtype(plan.algo.momentum_dtype)
    # the ServerState shape (see alg.init_state): the momentum bank always,
    # mirror/prev_grad only when the resolved StateLayout carries them
    # (dasha needs the variance-reduction slots; rosdhb/dgd/robust_dgd scan
    # momentum-only — the paper's per-client memory gap vs Byz-DASHA-PAGE,
    # 3x at [n, d] f32, see alg.server_state_bytes) — all sharded over the
    # server (coordinate) axes
    layout = plan.algo.resolved_state_layout()
    bank = _sds((n, d), mdt, mesh, P(None, sp.server_axes(mesh)))
    atk = _attack_state_specs(plan.algo, d, mesh)
    server = alg.ServerState(
        bank,
        bank if layout.mirror else None,
        (_sds((n, d), jnp.float32, mesh, P(None, sp.server_axes(mesh)))
         if layout.prev_grad else None),
        jax.ShapeDtypeStruct((), jnp.int32), atk)
    state = TrainState(
        params=params, server=server,
        step=jax.ShapeDtypeStruct((), jnp.int32),
        key=jax.ShapeDtypeStruct((2,), jnp.uint32))

    batch = _train_batch_specs(cfg, plan, mesh)
    return state, batch


def _train_batch_specs(cfg: ModelConfig, plan: TrainPlan, mesh: Mesh):
    n, lb, s = plan.n_workers, plan.local_batch, plan.shape.seq_len
    dp = P(sp.dp_axes(mesh))
    batch: Dict[str, Any] = {}
    if cfg.input_kind == "tokens":
        batch["tokens"] = _sds((n, lb, s), jnp.int32, mesh,
                               P(sp.dp_axes(mesh), None, None))
    else:
        batch["embeddings"] = _sds((n, lb, s, cfg.d_model),
                                   jnp.dtype(cfg.dtype), mesh,
                                   P(sp.dp_axes(mesh), None, None, None))
        batch["targets"] = _sds((n, lb, s), jnp.int32, mesh,
                                P(sp.dp_axes(mesh), None, None))
    if cfg.family == "vlm":
        batch["image_embeddings"] = _sds(
            (n, lb, cfg.n_image_tokens, cfg.d_model), jnp.dtype(cfg.dtype),
            mesh, P(sp.dp_axes(mesh), None, None, None))
    return batch


def stream_batch_specs(plan: TrainPlan, mesh: Mesh, chunk_size: int):
    """Abstract ``[chunk_size, ...]`` batch chunk for
    ``jit(build_chunked_train_step(...)).lower``: the per-round specs of
    :func:`_train_batch_specs` with a leading replicated round axis (the
    scan axis — every device sees every round, worker sharding unchanged).
    """
    per_round = _train_batch_specs(plan.model, plan, mesh)
    return jax.tree_util.tree_map(
        lambda s: _sds((chunk_size,) + s.shape, s.dtype, mesh,
                       P(*((None,) + s.sharding.spec))),
        per_round,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


# --------------------------------------------------------------------------
# serving
# --------------------------------------------------------------------------


def build_serve_step(spec: ArchSpec, shape: InputShape, mesh: Mesh):
    """Prefill or decode step. Signature:
       prefill: (params, batch, caches)      -> (logits_last, caches)
       decode:  (params, batch, caches, pos) -> (logits, caches)
    """
    cfg = model_for_shape(spec, shape)

    if shape.kind == "prefill":
        def prefill_step(params, batch, caches):
            hidden, caches, _ = tf.forward(params, cfg, batch,
                                           mode="prefill", pos=0,
                                           caches=caches, remat=False)
            logits = tf.logits_fn(params, cfg, hidden[:, -1:])
            return logits, caches
        return prefill_step

    def decode_step(params, batch, caches, pos):
        hidden, caches, _ = tf.forward(params, cfg, batch, mode="decode",
                                       pos=pos, caches=caches, remat=False)
        logits = tf.logits_fn(params, cfg, hidden)
        return logits, caches
    return decode_step


def serve_input_specs(spec: ArchSpec, shape: InputShape, mesh: Mesh):
    """Abstract (params, batch, caches[, pos]) for the serve step."""
    cfg = model_for_shape(spec, shape)
    abstract = _abstract_params(cfg)
    pspecs = sp.param_specs(abstract, mesh, fsdp=spec.fsdp)
    params = jax.tree_util.tree_map(
        lambda a, s: _sds(a.shape, a.dtype, mesh, s), abstract, pspecs,
        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))

    b = shape.global_batch
    dtype = jnp.dtype(cfg.dtype)
    if shape.kind == "prefill":
        s = shape.seq_len
        max_len = s
    else:
        s = 1
        max_len = shape.seq_len

    batch: Dict[str, Any] = {}
    if cfg.input_kind == "tokens":
        batch["tokens"] = _sds((b, s), jnp.int32, mesh,
                               sp.batch_spec(mesh, (b, s)))
    else:
        batch["embeddings"] = _sds((b, s, cfg.d_model), dtype, mesh,
                                   sp.batch_spec(mesh, (b, s, cfg.d_model)))
    if cfg.family == "vlm":
        batch["image_embeddings"] = _sds(
            (b, cfg.n_image_tokens, cfg.d_model), dtype, mesh,
            sp.batch_spec(mesh, (b, cfg.n_image_tokens, cfg.d_model)))

    abstract_caches = jax.eval_shape(
        functools.partial(tf.cache_init, cfg, b, max_len, dtype))
    caches = jax.tree_util.tree_map(
        lambda a: _sds(a.shape, a.dtype, mesh,
                       sp.cache_spec(mesh, a.shape, batch=b)),
        abstract_caches)

    if shape.kind == "prefill":
        return params, batch, caches
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    return params, batch, caches, pos
