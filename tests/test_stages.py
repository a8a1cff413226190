"""The train step's stages: the table from compiled instruction to stage
(``repro.core.stages``), on HLO written by hand and on the CPU compile of a
tiny dense model's step, plain and looped over a chunk of rounds."""

import dataclasses

import jax
import numpy as np
import pytest
from jax.sharding import AxisType, Mesh

from repro.core import stages

HAND = """\
HloModule m

%fused (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %m = f32[8]{0} multiply(%p, %p), metadata={op_name="jit(s)/aggregate/mul"}
}

%body (t: (s32[], f32[8])) -> (s32[], f32[8]) {
  %t = (s32[], f32[8]{0}) parameter(0)
  %i = s32[] get-tuple-element(%t), index=0
  %x = f32[8]{0} get-tuple-element(%t), index=1
  %one = s32[] constant(1)
  %j = s32[] add(%i, %one), metadata={op_name="jit(s)/update/add"}
  %y = f32[8]{0} sine(%x), metadata={op_name="jit(s)/vmap(transpose(jvp(grads)))/sin"}
  ROOT %r = (s32[], f32[8]{0}) tuple(%j, %y)
}

%cond (c: (s32[], f32[8])) -> pred[] {
  %c = (s32[], f32[8]{0}) parameter(0)
  %k = s32[] get-tuple-element(%c), index=0
  %n = s32[] constant(4)
  ROOT %lt = pred[] compare(%k, %n), direction=LT, metadata={op_name="jit(s)/update/lt"}
}

ENTRY %main (a: f32[8]) -> f32[8] {
  %a = f32[8]{0} parameter(0)
  %zero = s32[] constant(0)
  %hoisted = f32[8]{0} cosine(%a), metadata={op_name="jit(s)/cos"}
  %w0 = (s32[], f32[8]{0}) tuple(%zero, %hoisted)
  %w = (s32[], f32[8]{0}) while(%w0), condition=%cond, body=%body, metadata={op_name="jit(s)/while"}
  %out = f32[8]{0} get-tuple-element(%w), index=1
  %copy.1 = f32[8]{0} copy(%out)
  %fusion.2 = f32[8]{0} fusion(%copy.1), kind=kLoop, calls=%fused, metadata={op_name="jit(s)/flatten/x"}
  %cwtm.3 = f32[8]{0} custom-call(%fusion.2), custom_call_target="tpu_custom_call", metadata={op_name="jit(s)/aggregate/jit(cwtm)/cwtm/pallas_call"}
  ROOT %d = f32[8]{0} add(%cwtm.3, %cwtm.3), metadata={op_name="jit(s)/update/add"}
}
"""


@pytest.mark.parametrize("op_name,stage", [
    ("jit(step)/grads/vmap(jvp())/dot_general", "grads"),
    ("jit(step)/vmap(transpose(jvp(grads)))/mul", "grads"),
    ("jit(step)/sparsify/while/body/jit(compress)/compress/pallas_call",
     "sparsify"),
    ("jit(step)/update/grads/add", "update"),
    ("jit(step)/while/body/closed_call/cos", None),
])
def test_the_outermost_stage_names_an_op(op_name, stage):
    assert stages.stage_of(op_name) == stage


def test_hand_written_module():
    table = stages.parse(HAND)
    want = {
        "j": "update", "y": "grads", "lt": "update",
        # a fusion is its root's; a copy is what it reads
        "fusion.2": "aggregate", "copy.1": "grads",
        # out of its scope and out of the loop: the stage its reader in the
        # body names
        "hoisted": "grads",
        "cwtm.3": "aggregate", "d": "update"}
    assert {n: table[n].stage for n in want} == want
    assert table["cwtm.3"].kernel and not table["d"].kernel
    # a loop's own stage is none: its body runs several
    assert table["w"].stage is None
    # fused computations are not in the table
    assert "m" not in table


def test_the_table_is_built_when_read_and_once():
    class Compiled:
        calls = 0

        def as_text(self):
            Compiled.calls += 1
            return HAND

    before = stages._Record.compiled, stages._Record.table
    try:
        stages.record(Compiled())
        assert Compiled.calls == 0
        assert stages.last_table() is stages.last_table()
        assert Compiled.calls == 1
    finally:
        stages._Record.compiled, stages._Record.table = before


def test_scope_refuses_an_unknown_stage():
    with pytest.raises(ValueError, match="unknown stage"):
        stages.scope("exchange")


def _tiny_plan(devices, use_pallas):
    from repro.configs import get_arch
    from repro.configs.base import InputShape
    from repro.core import AggregatorConfig, AttackConfig, SparsifierConfig
    from repro.launch.steps import make_train_plan
    spec = get_arch("stablelm_3b")
    model = spec.model.reduced(n_layers=1, d_model=128)
    spec = dataclasses.replace(spec, model=model.with_overrides(
        use_flash_attention=use_pallas))
    mesh = Mesh(np.array(devices[:1]).reshape(1, 1), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
    overrides = {
        "name": "rosdhb", "f": 1, "momentum_dtype": "bfloat16",
        "server_compute_dtype": "bfloat16",
        "sparsifier": SparsifierConfig(kind="block", ratio=0.25,
                                       block_size=512, use_pallas=use_pallas),
        "aggregator": AggregatorConfig(name="cwtm", f=1,
                                       use_pallas=use_pallas),
        "attack": AttackConfig(name="alie")}
    return make_train_plan(spec, InputShape("train", 64, 4, "train"), mesh,
                           overrides, n_workers=4), mesh


@pytest.mark.parametrize("chunk_size,use_pallas", [
    (None, None), (3, None), (None, True)])
def test_every_op_of_a_compiled_step_has_a_stage(chunk_size, use_pallas):
    """On the CPU the kernels take their XLA paths, or (``use_pallas``)
    their interpret-mode Pallas bodies, which lower to loops of plain
    ops."""
    from repro.launch.train import compile_train_step
    plan, mesh = _tiny_plan(jax.devices(), use_pallas=use_pallas)
    compile_train_step(plan, mesh, chunk_size)
    table = stages.last_table()
    work = {n: op for n, op in table.items()
            if op.opcode not in stages.NO_WORK | stages.LOOPS}
    unstaged = sorted(n for n, op in work.items()
                      if op.stage not in stages.STAGES)
    assert not unstaged, unstaged
    assert {op.stage for op in work.values()} == set(stages.STAGES)
    # the step's loops are in it: the layer scan, and the chunk's rounds
    assert sum(op.opcode == "while" for op in table.values()) >= (
        2 if chunk_size else 1)
