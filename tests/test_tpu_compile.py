"""Compile the main path's Pallas kernels for a described TPU v5e.

Nothing runs: each test lowers and compiles a kernel at benchmark widths for
one chip of a ``v5e:2x2`` topology that is described, not attached, which
raises what the chip's compiler would raise (misaligned tiles, too much
VMEM or SMEM). Interpret mode cannot show those. The topology is described
inside a fixture, never at import, so every test worker collects the same
tests and only the worker that runs this file loads the TPU library.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

N, F, D, BLOCK, RATIO = 13, 3, 1 << 20, 512, 0.05


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler or library here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache but
    cannot be read back without the chip, so the cache is off around
    these compiles."""
    from jax.experimental.compilation_cache import compilation_cache
    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


@pytest.fixture
def compile_for(one_chip, no_persistent_cache):
    """``compile_for(fn, *shapes)``: compile ``fn`` for one described chip;
    each shape is ``(dims, dtype)``. Returns the compiled HLO text."""
    def run(fn, *shapes):
        args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
                for s, dt in shapes]
        return jax.jit(fn).lower(*args).compile().as_text()
    return run


@pytest.mark.parametrize("b", [1, 8])
@pytest.mark.parametrize("rule", ["cwtm", "median", "pairdist"])
def test_aggregation_kernels_compile(compile_for, rule, b):
    from repro.kernels.cwtm.cwtm import cwtm_pallas_batched
    from repro.kernels.median.median import median_pallas_batched
    from repro.kernels.pairdist.pairdist import pairdist_pallas_batched
    fn = {"cwtm": lambda x: cwtm_pallas_batched(x, F),
          "median": median_pallas_batched,
          "pairdist": pairdist_pallas_batched}[rule]
    hlo = compile_for(fn, ((b, N, D), jnp.float32))
    assert "tpu_custom_call" in hlo


# d = 2^27 holds 262,144 blocks: the momentum scatter's slot vector then
# exceeds what one call may prefetch into SMEM, so it runs as a chain of
# segments; compress and decompress are one call at any d
@pytest.mark.parametrize("d", [D, 1 << 27])
@pytest.mark.parametrize("op", ["compress", "decompress", "scatter"])
def test_randk_kernels_compile(compile_for, op, d):
    from repro.kernels.randk import randk as K
    kb = round(RATIO * (d // BLOCK))
    ids = ((kb,), jnp.int32)
    if op == "compress":
        hlo = compile_for(lambda g, i: K.block_compress(g, i, BLOCK, 20.0),
                          ((4, d), jnp.float32), ids)
    elif op == "decompress":
        hlo = compile_for(lambda p, i: K.block_decompress(p, i, BLOCK, d),
                          ((4, kb * BLOCK), jnp.float32), ids)
    else:
        hlo = compile_for(
            lambda m, p, i: K.momentum_scatter(m, p, i, BLOCK, 0.9),
            ((d,), jnp.float32), ((kb * BLOCK,), jnp.float32), ids)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("mask", ["global", "local"])
@pytest.mark.parametrize("op", ["compress", "decompress"])
def test_randk_bank_kernels_compile_at_stablelm_bank(compile_for, op, mask):
    """The cell's bank: 4 workers' [416,179,200] bf16 rows, Block-RandK
    0.05 / 512 (k = 40,642) under one shared id vector or one per row. The
    kernel reads and writes the [4, D] arrays as they are: the compiled
    program holds the one custom call, and no copy, pad or slice of them."""
    from repro.kernels.randk import randk as K
    n, d = 4, 416_179_200
    kb = round(RATIO * (d // BLOCK))
    assert kb == 40_642
    ids = ((kb,) if mask == "global" else (n, kb), jnp.int32)
    if op == "compress":
        hlo = compile_for(lambda g, i: K.block_compress(g, i, BLOCK, 20.0),
                          ((n, d), jnp.bfloat16), ids)
    else:
        hlo = compile_for(lambda p, i: K.block_decompress(p, i, BLOCK, d),
                          ((n, kb * BLOCK), jnp.bfloat16), ids)
    assert hlo.count("tpu_custom_call") == 1
    made = re.findall(r"= (bf16\[[\d,]+\])\S* ([\w-]+)\(", hlo)
    big = {f"bf16[{n},{d}]", f"bf16[{n},{kb * BLOCK}]"}
    opcodes = {opcode for shape, opcode in made if shape in big}
    assert "custom-call" in opcodes, opcodes
    assert opcodes <= {"parameter", "bitcast", "custom-call"}, opcodes


def test_flash_attention_compiles_at_stablelm_heads(compile_for):
    """32 heads x 4096 x head_dim 80 (padded to 128 inside the op), bf16."""
    from repro.kernels.flash_attention.ops import attention
    qkv = ((1, 4096, 32, 80), jnp.bfloat16)
    hlo = compile_for(lambda q, k, v: attention(q, k, v, use_pallas=True),
                      qkv, qkv, qkv)
    assert "tpu_custom_call" in hlo


def test_flash_train_attention_gradient_compiles(compile_for):
    """The training path differentiates through the kernel's forward."""
    from repro.models.layers import flash_train_attention

    def loss(q, k, v):
        out = flash_train_attention(q, k, v, 0, None, False)
        return jnp.sum(out.astype(jnp.float32))

    qkv = ((1, 2048, 32, 80), jnp.bfloat16)
    hlo = compile_for(jax.value_and_grad(loss, argnums=(0, 1, 2)),
                      qkv, qkv, qkv)
    assert "tpu_custom_call" in hlo


def test_bank_exchange_compiles_for_four_chips(topo, no_persistent_cache,
                                               monkeypatch):
    """The trainer's four-chip round: the sharded flatten's all-to-all and
    the coordinate-wise aggregation kernel mapped over the bank's shards.
    The all-to-all moves ``[chips, 8, c]`` pieces: a 2-D ``[chips, c]`` one
    compiles in time linear in ``c`` (89 s for a 33M-element bf16 array,
    against about a second in 3-D)."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    import repro.kernels
    from repro.core import AggregatorConfig, AlgorithmConfig
    from repro.launch.steps import _device_local_aggregator
    from repro.sharding import flatten as sf

    # the kernel dispatch takes its TPU branch, as on the chip
    monkeypatch.setattr(repro.kernels, "on_tpu", lambda: True)
    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"))
    leaf = jax.ShapeDtypeStruct((2048, 4096), jnp.float32)
    spec = sf.make_sharded_flat_spec({"w": leaf}, mesh, align=512)
    algo = AlgorithmConfig(n_workers=4, f=1, aggregator=AggregatorConfig(
        name="cwtm", f=1, use_pallas=True))
    agg = _device_local_aggregator(algo, mesh)

    def round_(g):
        return agg(sf.flatten_to_bank(g, spec, mesh, dtype=jnp.bfloat16))

    grads = {"w": jax.ShapeDtypeStruct(
        (4,) + leaf.shape, jnp.bfloat16,
        sharding=NamedSharding(mesh, P("data", None, None)))}
    with mesh:
        hlo = jax.jit(round_).lower(grads).compile().as_text()
    assert "bf16[4,8," in hlo.split("all-to-all(")[0].rsplit("\n", 1)[-1]
    assert "tpu_custom_call" in hlo


def test_train_step_kernels_keep_their_names_and_stages(
        topo, no_persistent_cache, monkeypatch):
    """The trainer's step at a small width: the four kernels the benchmark
    reads by name are custom-calls under those names, each in its stage
    of the program's table."""
    import dataclasses

    import numpy as np
    from jax.sharding import AxisType, Mesh

    import repro.kernels
    from repro.configs import get_arch
    from repro.configs.base import InputShape
    from repro.core import (AggregatorConfig, AttackConfig, SparsifierConfig,
                            stages)
    from repro.launch.steps import make_train_plan
    from repro.launch.train import compile_train_step

    # the kernel dispatch takes its TPU branch, as on the chip
    monkeypatch.setattr(repro.kernels, "on_tpu", lambda: True)
    spec = get_arch("stablelm_3b")
    spec = dataclasses.replace(spec, model=spec.model.reduced(
        n_layers=1, d_model=256).with_overrides(use_flash_attention=None))
    mesh = Mesh(np.array(topo.devices[:1]).reshape(1, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    plan = make_train_plan(
        spec, InputShape("train", 256, 4, "train"), mesh,
        {"f": 1, "momentum_dtype": "bfloat16",
         "server_compute_dtype": "bfloat16",
         "sparsifier": SparsifierConfig(kind="block", ratio=0.05,
                                        block_size=512),
         "aggregator": AggregatorConfig(name="cwtm", f=1),
         "attack": AttackConfig(name="alie")}, n_workers=4)
    compile_train_step(plan, mesh)
    kernels = {}
    for name, op in stages.last_table().items():
        if op.kernel:
            kernels.setdefault(name.rsplit(".", 1)[0], set()).add(op.stage)
    want = {"attention": {"grads"}, "compress": {"sparsify"},
            "decompress": {"sparsify"}, "cwtm": {"aggregate"}}
    assert {k: kernels.get(k) for k in want} == want, kernels
