"""Device time by the program's own named scopes (``jax.named_scope``).

A scope reaches the compiled program as the ``op_name`` of each
instruction's metadata (``jit(step)/while/body/moe/router/...``).
XLA fuses instructions into one kernel, whose event in the device trace
names the fusion.  A fusion keeps the metadata of the instruction it was
formed around (a matmul fused with the elementwise work after it carries
the matmul's), which the fused computation's root, the last elementwise
op, need not share: so a fusion is attributed by its own metadata, and by
its root's, read from the compiled HLO text, where it has none.
Instructions the compiler adds without metadata (layout copies, the
``copy-start`` / ``copy-done`` that prefetch weights) take the scope of
the first instruction that uses their result and has one.  Loops and
calls, which hold other operations, are left out.  A program without the
scopes gives nothing to read, never an error.
"""
from __future__ import annotations

import re

from reduce_trace import CONTAINERS

_HEADER = re.compile(r"^(?:ENTRY )?(%[\w.-]+) .*\{\s*$")
_INSTR = re.compile(r"^\s*(ROOT )?(%[\w.-]+) = ")
_CALLS = re.compile(r"calls=(%[\w.-]+)")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_OPERAND = re.compile(r"%[\w.-]+")


def op_names(hlo_text: str) -> tuple[str, dict[str, str]]:
    """``(module name, {instruction: op_name})`` over every instruction
    of a compiled program's HLO text; a fusion without one of its own
    takes its root's."""
    module = hlo_text.split(",", 1)[0].split()[-1]
    own: dict[str, str] = {}
    calls: dict[str, str] = {}
    root: dict[str, str] = {}
    users: dict[str, list[str]] = {}
    comp = None
    for line in hlo_text.splitlines():
        h = _HEADER.match(line)
        if h:
            comp = h.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name = m.group(2)
        op = _OP_NAME.search(line)
        if op:
            own[name] = op.group(1)
            if m.group(1) and comp is not None:
                root[comp] = op.group(1)
        c = _CALLS.search(line)
        if c:
            calls[name] = c.group(1)
        args = line.split(" = ", 1)[1].split(", metadata=", 1)[0]
        for operand in _OPERAND.findall(args):
            users.setdefault(operand, []).append(name)
    out = dict(own)
    for name, comp in calls.items():
        if name not in out and comp in root:
            out[name] = root[comp]
    # a chain of unnamed instructions (copy-start -> copy-done -> user)
    # is resolved from its named end, one link a pass
    for _ in range(4):
        found = {name: next(out[u] for u in us if u in out)
                 for name, us in users.items()
                 if name not in out and any(u in out for u in us)}
        if not found:
            break
        out.update(found)
    return module, out


def in_scope(op_name: str, scope: str) -> bool:
    return scope in op_name.split("/")


def scope_seconds(trace, hlo_text: str, scope: str) -> float | None:
    """Device seconds of the traced window's operations under ``scope``
    in the program of ``hlo_text``, averaged over the devices; nothing
    when no operation of that program carries the scope."""
    module, names = op_names(hlo_text)
    if not any(in_scope(n, scope) for n in names.values()):
        return None
    total = 0.0
    for ops in trace.device_ops.values():
        for e in ops:
            if e.module == module and e.opcode not in CONTAINERS \
                    and in_scope(names.get(e.instruction, ""), scope):
                total += e.seconds
    return total / max(len(trace.device_ops), 1)


def ms_per_evaluation(rec, scope: str) -> float | None:
    """Milliseconds of device time under ``scope`` per model evaluation
    of the traced window (the driver's ``step_hlo`` and
    ``traced_evaluations`` facts)."""
    hlo = rec.facts.get("step_hlo")
    evals = rec.facts.get("traced_evaluations")
    if rec.trace is None or not hlo or not evals:
        return None
    seconds = scope_seconds(rec.trace, hlo, scope)
    if not seconds:
        return None
    return 1e3 * seconds / evals
