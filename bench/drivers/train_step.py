"""Driver of the trainer's compiled step: ``compile_train_step`` over
``make_train_plan``, stepped round after round as the trainer's loop does
(a host clock around each step, synced by reading its loss).

Set-up compiles the step, makes the weights and the state on the device
from the seed, and drives that same compiled step through the first
``checked_steps`` rounds, where it reads what the comparison needs: each
step's loss, the momentum bank after round 1 (the first gradient as the
server received it, divided by 1 - beta: its norm per leaf, and its values
on a sample of the round's kept blocks) and the parameters' change after
the last checked round. The window then continues the same state. After
the window the program's state is freed and the plain reference runs the
checked rounds from the same weights, batches and round key.

The cell's traffic file gives the round (workers, f, attack, aggregator,
Block-RandK ratio and block, gamma, beta, sequence length, local batch)
and the limits; the configuration file gives the model, and names its
reference. The kernels are the backend's own choice: on a TPU a run
refuses to measure unless every one resolved to its Pallas kernel.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Any, Callable, Dict, List

import jax
import jax.numpy as jnp
import numpy as np

from bench import compare, weights
from bench.harness import ROOT, Cell, Run, Window, load_module, log


def model_config(conf: Dict[str, Any]):
    """The program's ``ModelConfig`` for the configuration file ``conf``.
    Raises where the program cannot run what the file states."""
    from repro.models.config import ModelConfig
    prec = conf["precision"]
    if conf["hidden_act"] != "silu" or conf.get("use_qkv_bias"):
        raise ValueError("the program runs a SwiGLU MLP without qkv bias")
    if conf["rope_pct"] != 1.0 or conf["norm_eps"] != 1e-6:
        raise ValueError("the program rotates whole heads and normalises "
                         "with eps 1e-6; the file must state those")
    return ModelConfig(
        name=conf["name"], family="dense",
        n_layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
        n_heads=conf["num_attention_heads"],
        n_kv_heads=conf["num_key_value_heads"],
        d_ff=conf["intermediate_size"], vocab_size=conf["vocab_size"],
        mlp="swiglu", norm="layernorm", rope_theta=conf["rope_theta"],
        tie_embeddings=conf["tie_word_embeddings"],
        use_flash_attention=None, dtype=prec["compute"])


def algo_overrides(tr: Dict[str, Any], prec: Dict[str, str]) -> Dict:
    from repro.core import AggregatorConfig, AttackConfig, SparsifierConfig
    return {
        "name": tr["algorithm"], "f": tr["f"], "gamma": tr["gamma"],
        "beta": tr["beta"], "momentum_dtype": prec["bank"],
        "server_compute_dtype": prec["server"],
        "sparsifier": SparsifierConfig(
            kind=tr["sparsifier"], ratio=tr["ratio"],
            block_size=tr["block_size"], use_pallas=None),
        "aggregator": AggregatorConfig(name=tr["aggregator"], f=tr["f"],
                                       use_pallas=None),
        "attack": AttackConfig(name=tr["attack"]),
    }


def ref_algo(tr: Dict[str, Any]) -> Dict[str, Any]:
    """The round as the reference takes it."""
    return {k: tr[k] for k in ("n_workers", "f", "ratio", "block_size",
                               "beta", "gamma")}


@dataclasses.dataclass
class Program:
    cell: Cell
    plan: Any
    compiled: Any
    abstract: Any
    state_sh: Any
    batch_sh: Any
    kernels: Dict[str, str]
    layout: Any
    compile_s: float


def build(cell: Cell, devices) -> Program:
    """Plan and compile the train step for one chip's share of the cell."""
    from jax.sharding import AxisType, Mesh
    from repro.configs.base import ArchSpec, InputShape
    from repro.launch.steps import make_train_plan, train_input_specs
    from repro.launch.train import compile_train_step, kernel_labels
    conf, tr = cell.config, cell.traffic
    spec = ArchSpec(model=model_config(conf),
                    citation=conf["source"])
    mesh = Mesh(np.array(devices).reshape(len(devices), 1),
                ("data", "model"), axis_types=(AxisType.Auto,) * 2)
    shape = InputShape("train", tr["seq_len"],
                       tr["n_workers"] * tr["local_batch"], "train")
    plan = make_train_plan(spec, shape, mesh,
                           algo_overrides(tr, conf["precision"]),
                           n_workers=tr["n_workers"])
    t0 = time.perf_counter()
    compiled = compile_train_step(plan, mesh)
    compile_s = time.perf_counter() - t0
    abstract = train_input_specs(plan, mesh)[0].params
    ref = reference(cell)
    layout = ref.Layout(abstract, tr["block_size"])
    if layout.padded != plan.flat_spec.padded_size:
        raise ValueError(f"flat layout {layout.padded} != the program's "
                         f"{plan.flat_spec.padded_size}")
    return Program(cell, plan, compiled, abstract,
                   compiled.input_shardings[0][0],
                   compiled.input_shardings[0][1], kernel_labels(plan),
                   layout, compile_s)


def reference(cell: Cell):
    return load_module(ROOT / cell.config["reference"])


def initial_state(prog: Program, seed: int):
    from repro.core import algorithms as alg
    from repro.launch.steps import TrainState
    params = weights.make_params(prog.abstract, seed,
                                 sharding=prog.state_sh.params)
    state = TrainState(
        params=params,
        server=alg.init_state(prog.plan.algo, prog.plan.flat_spec.padded_size),
        step=jnp.zeros((), jnp.int32),
        key=jnp.asarray(weights.key_words(seed, weights.ROUND_KEY)))
    return jax.device_put(state, prog.state_sh)


def batch(prog: Program, seed: int, step: int) -> Dict[str, np.ndarray]:
    tr = prog.cell.traffic
    return weights.token_batch(seed, step, tr["n_workers"],
                               tr["local_batch"], tr["seq_len"],
                               prog.cell.config["vocab_size"])


def _leaf_norms(layout) -> Callable:
    """Per-leaf l2 norm over all rows of a ``[n, D]`` bank."""
    @jax.jit
    def fn(bank):
        return jnp.stack([
            jnp.linalg.norm(bank[:, o:o + s].astype(jnp.float32))
            for o, s in zip(layout.offsets, layout.sizes)])
    return fn


@jax.jit
def _change_norms(p, p0):
    return jnp.stack([jnp.linalg.norm((a - b).astype(jnp.float32).ravel())
                      for a, b in zip(jax.tree_util.tree_leaves(p),
                                      jax.tree_util.tree_leaves(p0))])


#: Kept blocks of round 1 whose received values are compared one by one.
FIRST_SAMPLE = 1024


def first_sample(cell: Cell, seed: int):
    """Which of round 1's kept blocks are compared one by one, drawn from
    the seed: their positions among the kept blocks, and their block ids."""
    ref = reference(cell)
    n_blocks = shapes(cell)["D"] // cell.traffic["block_size"]
    kb = ref.n_selected(cell.traffic["ratio"], n_blocks)
    gen = np.random.default_rng([seed, weights.SAMPLE])
    pos = np.sort(gen.choice(kb, min(FIRST_SAMPLE, kb), replace=False))
    ids = ref.round_blocks(weights.key_words(seed, weights.ROUND_KEY),
                           n_blocks, kb, 1)[0]
    return pos, ids[pos]


@functools.partial(jax.jit, static_argnums=(2,))
def _bank_blocks(bank, ids, block: int):
    """Blocks ``ids`` of each row of the ``[n, D]`` bank, as float32
    ``[n, len(ids), block]``, read in place."""
    coords = (ids[:, None] * block + jnp.arange(block)[None, :]).reshape(-1)
    return bank[:, coords].astype(jnp.float32).reshape(
        bank.shape[0], ids.shape[0], block)


def checked_steps(prog: Program, state, seed: int):
    """Drive the compiled step through the cell's first ``checked_steps``
    rounds, reading what the comparison needs. Returns the state and the
    readings."""
    tr = prog.cell.traffic
    _, sample_ids = first_sample(prog.cell, seed)
    losses: List[float] = []
    for t in range(tr["checked_steps"]):
        state, metrics = prog.compiled(
            state, jax.device_put(batch(prog, seed, t), prog.batch_sh))
        losses.append(float(metrics["loss"]))
        if t == 0:
            bank = state.server.momentum
            first = np.asarray(_leaf_norms(prog.layout)(bank)) / (
                1.0 - tr["beta"])
            first_rows = np.asarray(_bank_blocks(
                bank, jnp.asarray(sample_ids), tr["block_size"])) / (
                1.0 - tr["beta"])
    # the weights again, from the call that made them: the same bits
    p0 = weights.make_params(prog.abstract, seed,
                             sharding=prog.state_sh.params)
    change = np.asarray(_change_norms(state.params, p0))
    del p0
    return state, {"loss": losses, "first_grad": first.tolist(),
                   "change": change.tolist(), "first_rows": first_rows}


def reference_readings(cell: Cell, seed: int, rnd=None) -> Dict[str, Any]:
    """The plain reference over the cell's checked rounds, from the same
    weights, batches and round key (``rnd``: the reference's rounding; the
    identity by default)."""
    ref = reference(cell)
    tr = cell.traffic
    abstract = _abstract(cell)
    params = weights.make_params(abstract, seed)
    batches = [weights.token_batch(seed, t, tr["n_workers"],
                                   tr["local_batch"], tr["seq_len"],
                                   cell.config["vocab_size"])["tokens"]
               for t in range(tr["checked_steps"])]
    return ref.train_readings(
        params, batches, weights.key_words(seed, weights.ROUND_KEY),
        cell.config, ref_algo(tr), rnd=rnd or ref.identity,
        sample=first_sample(cell, seed)[0])


def _abstract(cell: Cell):
    """The parameter tree's shapes, as the program lays it out."""
    from repro.models import model_init
    cfg = model_config(cell.config)
    return jax.eval_shape(lambda: model_init(jax.random.PRNGKey(0), cfg))


def memory_peak(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def memory_limit(devices) -> int:
    return min(int((d.memory_stats() or {}).get("bytes_limit", 0))
               for d in devices)


def run(cell: Cell, *, seed: int, seconds: float, window: Window,
        devices) -> Run:
    tr = cell.traffic
    prog = build(cell, devices)
    log(f"compiled the step in {prog.compile_s:.1f} s; kernels "
        f"{prog.kernels}; D = {prog.plan.flat_spec.padded_size:,}")
    mem = prog.compiled.memory_analysis()
    if mem is not None:
        log(f"the compiled step holds {mem.argument_size_in_bytes / 2**30:.2f}"
            f" GiB of arguments and {mem.temp_size_in_bytes / 2**30:.2f} GiB "
            f"of temporaries")
    if devices[0].platform == "tpu" and \
            set(prog.kernels.values()) != {"pallas"}:
        raise RuntimeError(f"kernels resolved to {prog.kernels}, not the "
                           f"Pallas kernels the cell states")
    state = initial_state(prog, seed)
    state, readings = checked_steps(prog, state, seed)
    tokens_per_step = tr["n_workers"] * tr["local_batch"] * tr["seq_len"]

    step_s: List[float] = []
    failed = 0
    window.begin()
    t_start = prev = time.perf_counter()
    while prev - t_start < seconds:
        with window.span("batch"):
            b = batch(prog, seed, tr["checked_steps"] + len(step_s))
        with window.span("device_put"):
            b = jax.device_put(b, prog.batch_sh)
        with window.span("dispatch"):
            state, metrics = prog.compiled(state, b)
        with window.span("wait"):
            loss = float(metrics["loss"])
        failed += not np.isfinite(loss)
        now = time.perf_counter()
        step_s.append(now - prev)
        prev = now
    window.end()
    steps, elapsed = len(step_s), prev - t_start
    peak = memory_peak(devices)
    log(f"window: {steps} steps in {elapsed:.3f} s (a step: min "
        f"{min(step_s):.4f} s, median {np.median(step_s):.4f} s, max "
        f"{max(step_s):.4f} s at step {int(np.argmax(step_s))}); last loss "
        f"{loss:.5f}; peak {peak / 1e9:.3f} GB of "
        f"{memory_limit(devices) / 1e9:.3f} GB")
    del state, metrics, b, prog
    t_ref = time.perf_counter()
    ref = reference_readings(cell, seed)
    log(f"reference: {time.perf_counter() - t_ref:.1f} s; losses "
        f"{ref['loss']} vs the program's {readings['loss']}")
    gaps = compare.training_gaps(readings, ref)
    return Run(
        attempted=steps, failed=failed,
        metrics={"train_tokens_per_s": steps * tokens_per_step / elapsed},
        counters={"steps": steps, "window_s": elapsed,
                  "tokens": steps * tokens_per_step,
                  "shapes": shapes(cell)},
        checks=compare.checks(gaps, tr["limits"]),
        memory_peak_bytes=peak)


def shapes(cell: Cell) -> Dict[str, Any]:
    """The shapes per-layer readers compute operations and bytes from."""
    conf, tr = cell.config, cell.traffic
    ref = reference(cell)
    layout = ref.Layout(_abstract(cell), tr["block_size"])
    return {
        "n_layers": conf["num_hidden_layers"],
        "d_model": conf["hidden_size"], "d_ff": conf["intermediate_size"],
        "n_heads": conf["num_attention_heads"],
        "n_kv_heads": conf["num_key_value_heads"],
        "vocab": conf["vocab_size"], "seq_len": tr["seq_len"],
        "local_batch": tr["local_batch"], "n_workers": tr["n_workers"],
        "D": layout.padded, "block_size": tr["block_size"],
        "k_blocks": ref.n_selected(tr["ratio"], layout.n_blocks),
        "bank_bytes": jnp.dtype(conf["precision"]["bank"]).itemsize,
    }
