"""Which named phase of a program each compiled instruction belongs to.

A device trace (XProf, `jax.profiler`) names each executed operation after
its instruction in the compiled module: `%copy.70`, `%fusion.12`,
`%fxp_mlp_train_step_critic.4`.  A program names its phases with
`jax.named_scope`, and a scope reaches the compiled module only as a
component of each instruction's `op_name` metadata.  `op_phases` reads the
module's text (`jax.stages.Compiled.as_text()`, the program that runs) and
maps every instruction to a phase, so a trace's operations can be summed
per phase by instruction name.

  * An instruction's phase is the first component of its `op_name` that is
    one of the phases.  Autodiff renders a scope that it differentiates
    whole as `jvp(<scope>)`, and its backward as `transpose(jvp(<scope>))`:
    such a component counts as the scope it wraps.
  * An instruction with none, such as a layout `copy` the compiler
    inserted, takes the phase of its users when they all agree on one.
    Users are followed through `copy-start`/`copy-done` and any other
    instruction without a phase of its own.
  * Every other instruction is `UNSCOPED`.
"""
from __future__ import annotations

import re
from typing import Iterable

UNSCOPED = "unscoped"

_INSTRUCTION = re.compile(r"\s*(?:ROOT\s+)?%([\w.\-]+) = ")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_NAME = re.compile(r"%([\w.\-]+)")
_TRANSFORMED = re.compile(r"^(?:jvp|transpose)\((.*)\)$")


def _scope(component: str) -> str:
    """`transpose(jvp(ffn))` -> `ffn`; any other component as it is."""
    while (m := _TRANSFORMED.match(component)) is not None:
        component = m.group(1)
    return component


def _group_end(s: str, i: int) -> int:
    """The index just past the bracket group that opens at `s[i]`."""
    depth = 0
    for j in range(i, len(s)):
        if s[j] in "([{":
            depth += 1
        elif s[j] in ")]}":
            depth -= 1
            if depth == 0:
                return j + 1
    return len(s)


def _operands(rhs: str) -> list[str]:
    """The operand names in an instruction's text after `= `: the shape (a
    tuple shape is one bracket group), the opcode, then the operand list.
    What follows it (`calls=`, `body=`, metadata) names no operand."""
    i = _group_end(rhs, 0) if rhs.startswith("(") else rhs.find(" ")
    i = rhs.find("(", i)
    return [] if i < 0 else _NAME.findall(rhs[i:_group_end(rhs, i)])


def op_phases(hlo_text: str, phases: Iterable[str]) -> dict[str, str]:
    """{instruction name (no `%`): phase or `UNSCOPED`} for every
    instruction of a compiled HLO module's text."""
    wanted = frozenset(phases)
    own: dict[str, str] = {}
    users: dict[str, list[str]] = {}
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            continue
        rhs = line[m.end():]
        meta = _OP_NAME.search(rhs)
        parts = [_scope(p) for p in meta.group(1).split("/")] if meta else ()
        own[m.group(1)] = next((p for p in parts if p in wanted), UNSCOPED)
        for operand in _operands(rhs):
            users.setdefault(operand, []).append(m.group(1))
    # a computation lists an instruction after its operands, so in reverse
    # every user is settled before the instructions it reads
    out: dict[str, str] = {}
    for name in reversed(own):
        phase = own[name]
        if phase == UNSCOPED:
            seen = {out.get(u, UNSCOPED) for u in users.get(name, ())}
            if len(seen) == 1:
                phase = seen.pop()
        out[name] = phase
    return out


__all__ = ["UNSCOPED", "op_phases"]
