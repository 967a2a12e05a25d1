"""From compiled HLO to the program's named scopes.

The program names each layer of its training step with
``jax.named_scope`` (``model.embed``, ``model.layers``,
``model.attention``, ``model.mlp``, ``model.head_loss``, ``sync.pack``,
``sync.reduce_scatter``, ``sync.update``, ``sync.allgather``,
``sync.unpack``, ``sync.elastic``). JAX writes the scope path into each
HLO instruction's ``metadata={op_name="..."}``, and XLA keeps it through
its passes: a fusion carries its root instruction's ``op_name``. The
trace names a device operation by its instruction, so ``op_scopes``
maps each instruction to the scope path it ran under, and ``in_scope``
asks whether that path lies inside a scope.

Some instructions carry no ``op_name``: XLA passes that create them
(copies, bitcast and convert fusions, some loop-carried updates) drop
it, or give the enclosing transform's (``jit(step)/shard_map/...``),
which names none of the program's scopes. Such an instruction inherits
one, from the first of these that agree: for a fusion, the instructions
of its fused computation (less its constants and parameters); its users
in the same computation; its operands there. Users and operands are
followed through instructions that have no scoped ``op_name``
themselves. Names agree when they share a scope: the inherited name is
the first of them if all lie in the same scopes, else the path of the
scopes they all share (an op of the scan's MLP and one of its loop
bookkeeping give ``model.layers``). An instruction whose neighbours
share no scope, or have no name, stays unscoped.
"""
from __future__ import annotations

import functools
import re

#: the program's scopes, as ``jax.named_scope`` names them
SCOPES = ("model.embed", "model.layers", "model.attention", "model.mlp",
          "model.head_loss", "sync.pack", "sync.reduce_scatter",
          "sync.update", "sync.allgather", "sync.unpack", "sync.elastic")
#: instruction kinds that do the work of a step on the device
COMPUTE = ("fusion", "dot", "convolution", "gather", "scatter",
           "custom-call")

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*.*?\s([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_REF = re.compile(r"%([\w.\-]+)")
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_WRAPPED = re.compile(r"^[\w\-]+\((.*)\)$")


def parse(hlo: str) -> dict:
    """Every instruction of compiled HLO text: ``{name: (opcode,
    op_name or None, operand names, computation, whether the op_name is
    its own)}``; operands are the instructions of the same computation
    that it reads. A fusion with no ``op_name`` of its own takes the one
    its fused computation's instructions agree on."""
    out, comp, names = {}, None, set()
    lines, inner = [], {}
    for line in hlo.splitlines():
        if line.startswith("}"):
            comp = None
            continue
        m = None if line.startswith(" ") else _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        if comp is None:
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        name, opcode = m.group(1), m.group(2)
        op = _OP_NAME.search(line)
        op = op.group(1) if op else None
        lines.append((name, opcode, op, comp, line))
        names.add((comp, name))
        if opcode not in ("constant", "parameter"):
            inner.setdefault(comp, []).append(op)
    for name, opcode, op, comp, line in lines:
        refs = [r for r in _REF.findall(line[line.index("=") + 1:])
                if (comp, r) in names and r != name]
        own, called = op is not None, _CALLS.search(line)
        if not own and opcode == "fusion" and called:
            op = _agreed(inner.get(called.group(1), ()))
        out[name] = (opcode, op, tuple(dict.fromkeys(refs)), comp, own)
    return out


def _components(op_name: str) -> list:
    """The scope path's components, each unwrapped from the transforms
    around it (``transpose(jvp(model.mlp))`` -> ``model.mlp``)."""
    parts = []
    for part in re.split(r"[/;]", op_name):
        while True:
            m = _WRAPPED.match(part)
            if not m:
                break
            part = m.group(1)
        parts.append(part)
    return parts


def in_scope(op_name: str | None, scope: str) -> bool:
    """Whether ``op_name`` lies inside ``scope``: a whole component of its
    path, bare or wrapped in transforms."""
    return bool(op_name) and scope in _components(op_name)


def signature(op_name: str | None) -> tuple:
    """The program's scopes that ``op_name`` lies in."""
    if not op_name:
        return ()
    parts = set(_components(op_name))
    return tuple(s for s in SCOPES if s in parts)


def _agreed(names) -> str | None:
    """What ``names`` agree on, ignoring the missing: the first of them,
    if all lie in the same scopes; else the path of the scopes they all
    share; None if they share none, or none is given."""
    names = [n for n in names if n]
    if not names:
        return None
    sigs = {signature(n) for n in names}
    if len(sigs) == 1:
        return names[0] if sigs != {()} else None
    shared = [s for s in SCOPES if all(s in sig for sig in sigs)]
    return "/".join(shared) or None


@functools.lru_cache(maxsize=4)
def resolve(hlo: str) -> dict:
    """``{instruction: (op_name or None, inherited)}`` for every
    instruction of the compiled text."""
    ins = parse(hlo)
    users: dict = {n: [] for n in ins}
    for n, (_, _, refs, _, _) in ins.items():
        for r in refs:
            users[r].append(n)

    def walk(edges):
        """Each instruction's own op_name, else the one its neighbours
        along ``edges`` agree on, followed through those without one."""
        memo, opened = {}, set()
        for start in ins:
            stack = [start]
            while stack:
                n = stack[-1]
                if n in memo:
                    stack.pop()
                elif signature(ins[n][1]):
                    memo[n] = ins[n][1]
                elif n not in opened:
                    opened.add(n)
                    stack += [m for m in edges(n) if m not in memo]
                else:
                    # the neighbours are done (a cycle's back edge: None)
                    memo[n] = (_agreed(memo.get(m) for m in edges(n))
                               or ins[n][1])
        return memo

    up = walk(lambda n: users[n])
    down = walk(lambda n: ins[n][2])
    out = {}
    for n, (_, op, _, _, own) in ins.items():
        got = op if signature(op) else \
            next((x for x in (up[n], down[n]) if signature(x)), op)
        out[n] = (got, got is not None and not (own and got == op))
    return out


def op_scopes(hlo: str) -> dict:
    """``{instruction: op_name}``: each instruction's own ``op_name``, or
    the one it inherits; None for an instruction left unscoped."""
    return {n: op for n, (op, _) in resolve(hlo).items()}


def inherited(hlo: str) -> set:
    """The instructions whose ``op_name`` came by inheritance."""
    return {n for n, (op, how) in resolve(hlo).items() if how}


def compute_ops(hlo: str) -> list:
    """The instructions that do a step's work: fusions, dots,
    convolutions, gathers, scatters and custom calls."""
    return [n for n, (opcode, *_) in parse(hlo).items()
            if opcode in COMPUTE]


def _step_op(ctx, n: str) -> bool:
    """Neither a Pallas kernel nor a collective: the ops ``step.xla_ms``
    counts."""
    return not (n in ctx["kernel_ops"] or ctx["lib"].is_collective(n))


def scope_ms(ctx, *scopes: str) -> float | None:
    """Device time per step, in ms, of the step's XLA ops (no Pallas
    kernel, no collective) whose scope path lies in any of ``scopes``;
    None without a trace or a compiled program, or if no op lies there."""
    trace, hlo = ctx["trace"], ctx["run"].get("hlo")
    if trace is None or not hlo or not ctx["steps"]:
        return None
    names = op_scopes(hlo)
    hit = {n for n, op in names.items()
           if op and any(in_scope(op, s) for s in scopes)}
    if not hit:
        return None
    seconds = ctx["lib"].op_seconds(
        trace, lambda n: n in hit and _step_op(ctx, n))
    return seconds / ctx["steps"] * 1e3


def unscoped_ms(ctx) -> float | None:
    """The rest of ``step.xla_ms``, in ms a step: its device time less
    that of the step's ops that lie in one of the program's scopes. That
    is the ops in none (or that the compiled program does not name), and
    the time in which an op that holds others, such as a loop, ran with
    none of them, which the trace leaves unnamed. None without a trace or
    a compiled program, or for a program that names no scope."""
    trace, hlo = ctx["trace"], ctx["run"].get("hlo")
    if trace is None or not hlo or not ctx["steps"]:
        return None
    scoped = {n for n, op in op_scopes(hlo).items() if signature(op)}
    if not scoped:
        return None          # a program without scopes
    lib = ctx["lib"]
    step = lib.busy_s(trace, leave_out=lambda n: not _step_op(ctx, n))
    inside = lib.op_seconds(trace, lambda n: n in scoped and _step_op(ctx, n))
    return (step - inside) / ctx["steps"] * 1e3
