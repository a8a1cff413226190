"""The stages of one robust round, named where they run.

Each stage of the train step runs inside ``jax.named_scope(<stage>)``
(:func:`scope`). The scope is recorded in the compiled HLO's op metadata
and costs nothing at run time. :func:`parse` turns a compiled executable's
HLO text into ``{instruction name: Op(stage, opcode)}``, so that device
time read by instruction name (a profiler trace) can be summed by stage:

- an instruction's stage is the outermost stage in its ``op_name``, also
  inside transform wrappers such as ``transpose(jvp(grads))``;
- a fusion belongs to the stage of its fused computation's root;
- an instruction that names no stage takes the stage of the nearest
  instruction whose value it reads that names one, else of the nearest
  linked to it either way, else that of the loop that runs it. Such are
  the instructions a compiler pass adds (copies, async copies and slices,
  layout changes, a loop's hoisted constants and counters) and the
  constants that differentiation moves out of their scope. Values are
  followed through tuples and loops element by element;
- the entry computation, and every while body and condition, conditional
  branch and call it reaches, are covered: fused computations and
  reducers are not instructions a trace times.

:func:`record` keeps a reference to the train step compiled last
(``repro.launch.train.compile_train_step`` calls it); :func:`last_table`
parses it when asked, once.
"""

from __future__ import annotations

import re
from collections import defaultdict
from typing import Dict, List, NamedTuple, Optional

import jax

#: The stages of the train step, in the order a round runs them.
STAGES = ("grads", "flatten", "sparsify", "attack", "aggregate", "update")

#: Opcodes that run computations of their own, whose instructions are the
#: work.
LOOPS = frozenset({"while", "conditional", "call"})

#: Opcodes that run nothing on the device.
NO_WORK = frozenset({"parameter", "constant", "tuple", "get-tuple-element",
                     "bitcast", "opt-barrier", "after-all"})

_COMPUTATION = re.compile(r"^(ENTRY )?%?([\w.\-]+) .*\{$")
_INSTRUCTION = re.compile(
    r"^\s*(ROOT )?%?([\w.\-]+) = .*?(?<![\w\-])([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(
    r"\b(body|condition|calls|to_apply|true_computation|false_computation|"
    r"branch_computations)=(\{[^}]*\}|%?[\w.\-]+)")
_NAME = re.compile(r"%([\w.\-]+)")
_WRAPPERS = re.compile(r"^(?:[\w\-]+\()+|\)+$")
_INDEX = re.compile(r"\), index=(\d+)")


class Op(NamedTuple):
    """One instruction of a compiled step: its stage (``None`` outside
    every stage) and its opcode."""

    stage: Optional[str]
    opcode: str

    @property
    def kernel(self) -> bool:
        return self.opcode == "custom-call"


def scope(stage: str):
    """The named scope of ``stage``, one of :data:`STAGES`."""
    if stage not in STAGES:
        raise ValueError(f"unknown stage {stage!r}; stages: {STAGES}")
    return jax.named_scope(stage)


def stage_of(op_name: str) -> Optional[str]:
    """The outermost stage named in an ``op_name`` path, or ``None``."""
    for part in op_name.split("/"):
        name = _WRAPPERS.sub("", part)
        if name in STAGES:
            return name
    return None


class _Inst(NamedTuple):
    comp: str
    opcode: str
    stage: Optional[str]       # named by its op_name (a fusion: its root's)
    calls: Dict[str, List[str]]
    args: List[str]
    index: Optional[int]       # a get-tuple-element's, or a parameter's


def _instructions(hlo_text: str):
    """``({name: _Inst}, {computation: its root's name}, entry's name)``."""
    insts: Dict[str, _Inst] = {}
    roots: Dict[str, str] = {}
    entry = comp = None
    for line in hlo_text.splitlines():
        c = _COMPUTATION.match(line)
        if c:
            comp = c.group(2)
            entry = comp if c.group(1) else entry
            continue
        m = _INSTRUCTION.match(line) if comp else None
        if not m:
            continue
        meta = _OP_NAME.search(line)
        operands, attrs, depth = line[m.end():], "", 1
        for k, ch in enumerate(operands):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if not depth:
                operands, attrs = operands[:k], operands[k:]
                break
        index = _INDEX.match(attrs) if m.group(3) == "get-tuple-element" \
            else None
        insts[m.group(2)] = _Inst(
            comp, m.group(3), stage_of(meta.group(1)) if meta else None,
            {k: re.findall(r"[\w.\-]+", v) for k, v in _CALLED.findall(attrs)},
            _NAME.findall(operands), int(index.group(1)) if index else
            int(operands) if m.group(3) == "parameter" else None)
        if m.group(1):
            roots[comp] = m.group(2)
    for name, i in insts.items():
        if i.opcode == "fusion" and i.calls.get("calls"):
            root = insts.get(roots.get(i.calls["calls"][0], ""))
            if root is not None and root.stage:
                insts[name] = i._replace(stage=root.stage)
    return insts, roots, entry


def _nearest(start: str, edges, named, conduit) -> Optional[str]:
    """The stage of the nearest instruction along ``edges`` that names
    one, passing only through ``conduit`` instructions."""
    frontier, seen = [start], {start}
    while frontier:
        nxt = []
        for n in frontier:
            for m in edges[n]:
                if named[m] is not None:
                    return named[m]
                if m not in seen and m in conduit:
                    seen.add(m)
                    nxt.append(m)
        frontier = nxt
    return None


def parse(hlo_text: str) -> Dict[str, Op]:
    """``{instruction name: Op}`` over the instructions a trace times: those
    of the entry computation and of the computations it runs (while
    bodies and conditions, conditional branches, calls), transitively."""
    every, roots, entry = _instructions(hlo_text)
    members: Dict[str, List[str]] = defaultdict(list)
    for n, i in every.items():
        members[i.comp].append(n)
    caller: Dict[str, Optional[str]] = {entry: None}
    order = [entry]
    for comp in order:
        for n in members[comp]:
            if every[n].opcode in LOOPS:
                for c in sum(every[n].calls.values(), []):
                    if c in roots and c not in caller:
                        caller[c] = n
                        order.append(c)
    insts = {n: every[n] for c in order for n in members[c]}

    def runs(i: _Inst) -> str:
        """The root of the computation a loop or call runs."""
        return roots.get((i.calls.get("body") or i.calls["to_apply"])[0], "")

    def runner(i: _Inst) -> Optional[_Inst]:
        r = insts.get(caller.get(i.comp) or "")
        return r if r is not None and r.opcode in ("while", "call") else None

    def element(tup: str, k: int) -> List[str]:
        """Element ``k`` of a tuple, or of the tuple a call was given."""
        i = insts.get(tup)
        if i is not None and i.opcode == "parameter":
            r = runner(i)
            if r is not None and r.opcode == "call":
                return element(r.args[i.index], k)
        return i.args[k:k + 1] if i is not None and i.opcode == "tuple" \
            else []

    def source(i: _Inst) -> List[str]:
        """Where a value comes from: a call's parameter from the call's
        operand; an element of a tuple, of a loop's or call's result, or
        of a loop's carry (its operand, or the last iteration's result)."""
        if i.opcode == "parameter":
            r = runner(i)
            return r.args[i.index:i.index + 1] if r is not None else []
        a = insts.get(i.args[0]) if i.args else None
        if i.opcode != "get-tuple-element" or a is None:
            return i.args
        if a.opcode == "tuple":
            return element(i.args[0], i.index)
        if a.opcode in ("while", "call"):
            return element(runs(a), i.index)
        r = runner(a) if a.opcode == "parameter" else None
        if r is None:
            return i.args
        return element(r.args[a.index], i.index) + (
            element(runs(r), i.index) if r.opcode == "while" else [])

    reads = {n: [a for a in source(i) if a in insts]
             for n, i in insts.items()}
    readers: Dict[str, List[str]] = {n: [] for n in insts}
    for n, args in reads.items():
        for a in args:
            readers[a].append(n)
    either = {n: reads[n] + readers[n] for n in insts}
    named = {n: i.stage for n, i in insts.items()}
    # values pass through these: not through a loop, which runs several
    # stages, nor a tuple, an entry parameter or a constant, which many
    # read
    conduit = {n for n, i in insts.items() if i.opcode not in LOOPS
               and i.opcode not in ("tuple", "constant") and
               (i.opcode != "parameter" or reads[n])}
    stage = {n: s if s is not None or n not in conduit else
             _nearest(n, reads, named, conduit)
             or _nearest(n, either, named, conduit)
             for n, s in named.items()}
    for comp in order[1:]:
        for n in members[comp]:
            stage[n] = stage[n] or stage[caller[comp]]
    return {n: Op(stage[n], i.opcode) for n, i in insts.items()}


class _Record:
    """The train step compiled last in this process: one per process, as
    a trace's readers (the benchmark's per-layer metrics) are handed no
    executable."""

    compiled = None
    table: Optional[Dict[str, Op]] = None


def record(compiled) -> None:
    """Keep ``compiled`` as the train step compiled last; nothing is
    parsed until :func:`last_table` is called."""
    _Record.compiled, _Record.table = compiled, None


def last_table() -> Optional[Dict[str, Op]]:
    """:func:`parse` of the train step compiled last (parsed once), or
    ``None`` where none was compiled in this process."""
    if _Record.compiled is None:
        return None
    if _Record.table is None:
        _Record.table = parse(_Record.compiled.as_text())
    return _Record.table
