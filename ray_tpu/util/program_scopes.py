"""Which piece of the model each instruction of a compiled program is: the
join's left side of *scope -> compiled instruction -> trace event*.

The device programs carry ``jax.named_scope``s from one vocabulary
(``ops/scopes.py``). A scope is in no event's NAME in a device trace (an
operation's event is named by its HLO text) but it is in the compiled
program's text: every instruction prints ``metadata={op_name="jit(f)/
while/body/moe_combine/mul"}``, fusions included. ``instruction_scopes``
reads that text into ``{instruction: [result shape, scope]}`` (and which
of the scopes are not the instruction's own: the one rule, for what the
compiler made), and
``record_programs`` keeps one such map for each executable of the named
programs that the backend still holds, in ``util/tracing.py``'s store
(``tracing.recorded_scopes()``): whoever holds a profiler's trace of this
process joins its ``XLA Ops`` events to it by instruction name and shape.
Taken once, outside every window (``PagedLLMEngine.stop``, the end of
``JaxTrainer.fit``), and only where a traced slice dispatched something:
it costs ``to_string()`` of those programs. A map says what the
EXECUTABLE was compiled with: one that a persistent compile cache handed
back (its key strips locations and name stacks) carries the scopes of
the tree that compiled it. docs/tracing_plane.md, 1a."""

from __future__ import annotations

import re
import time
from typing import NamedTuple

from ray_tpu.ops.scopes import VOCABULARY
from ray_tpu.util import tracing

_VOCABULARY = frozenset(VOCABULARY)
# ``transpose(jvp(attn_qkv))`` is the scope ``attn_qkv`` under two
# transformations; ``jit(norm)`` is a jitted FUNCTION of that name
# (``jnp.linalg.norm``'s own) and no scope
_TRANSFORMED = re.compile(r"^(?!p?jit\()\w+\((.*)\)$")
# one line of a module's text: ``[ROOT] %name = shape opcode(operands),
# attributes``; a computation's first line: ``[ENTRY] %name (parameters)``
_INSTRUCTION = re.compile(
    r"^\s+(?:ROOT )?%(?P<name>\S+) = (?P<shape>\(*[a-z]+[0-9]*\[[0-9,]*\])?"
    r".*?(?:^|\s)(?P<opcode>[a-z][a-z0-9\-]*)\((?P<operands>[^)]*)\)"
    r"(?P<attributes>.*)$")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%(\S+) \(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(r"(calls|to_apply)=%([^\s,)]+)")
_OPERAND = re.compile(r"%([^\s,)]+)")


class _Instruction(NamedTuple):
    name: str
    shape: str              # ``dtype[dims]``, a tuple's first
    scope: str | None       # of its own ``op_name``; None: it has none
    operands: list


def scope_of(op_name: str) -> str:
    """The INNERMOST vocabulary name on an instruction's ``op_name`` path,
    or "": ``jit(main)``, ``while/body`` and every other part are skipped,
    a transformed scope counts as the scope."""
    for part in reversed(op_name.split("/")):
        while (inner := _TRANSFORMED.match(part)):
            part = inner.group(1)
        if part in _VOCABULARY:
            return part
    return ""


def instruction_scopes(hlo_text: str) -> tuple:
    """``({instruction name: [result shape, scope]}, {instruction name:
    rule})`` for every instruction of every computation of a compiled
    module's text that runs by itself: not those inside a fused
    computation, nor a reduction's or a sort's applied one. The shape is
    ``dtype[dims]`` (a tuple's first), as a trace event's name begins.
    The scope is ``scope_of`` the instruction's own ``op_name``, "" where
    that path holds no name of the vocabulary. ONE rule beside it, for an
    instruction the COMPILER made (no ``op_name`` at all: a prefetch's
    ``copy-start`` and ``copy-done``, a rematerialised copy, a fusion
    round a scatter it rewrote): the scope of its first operand that has
    one (``"operand"``), else of its first user (``"user"``), since what
    moves a piece's value is that piece's. The second dict names the
    instructions that took a scope so and by which, so that whoever sums
    a scope's time can say how much of it is inferred."""
    computations, inside = {}, set()
    rows = None
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head:
            rows = computations.setdefault(head.group(1), [])
            continue
        found = _INSTRUCTION.match(line) if rows is not None else None
        if found is None:
            continue
        opcode, attributes = found["opcode"], found["attributes"]
        op_name = _OP_NAME.search(attributes)
        called = _CALLED.search(attributes)
        if called and (opcode == "fusion" or (called.group(1) == "to_apply"
                                              and opcode != "call")):
            inside.add(called.group(2))
        rows.append(_Instruction(
            found["name"], (found["shape"] or "").lstrip("("),
            None if op_name is None else scope_of(op_name.group(1)),
            _OPERAND.findall(found["operands"])))
    out, inferred = {}, {}
    for name, rows in computations.items():
        if name in inside:
            continue
        scopes = {row.name: row.scope for row in rows}
        users = {}
        for row in rows:
            for operand in row.operands:
                users.setdefault(operand, []).append(row.name)
        for row in rows:                    # made by the compiler: operands
            if scopes[row.name] is None:
                _infer(scopes, inferred, row.name, row.operands, "operand")
        for row in reversed(rows):          # else users
            if scopes[row.name] is None:
                _infer(scopes, inferred, row.name,
                       users.get(row.name, ()), "user")
        for row in rows:
            out[row.name] = [row.shape, scopes[row.name] or ""]
    return out, inferred


def _infer(scopes: dict, inferred: dict, name: str, near, rule: str) -> None:
    scope = next((scopes[n] for n in near if scopes.get(n)), None)
    if scope:
        scopes[name], inferred[name] = scope, rule


def record_programs(names) -> int:
    """One record (``tracing.record_scopes``) for each executable the
    backend holds whose module is named in ``names`` (``jit_<program>``;
    a prefill program has one executable a ``group x bucket``); returns
    how many, and emits ``program.scopes`` with what that took."""
    import jax

    start = time.time()
    kept = instructions = 0
    for executable in jax.devices()[0].client.live_executables():
        try:
            module = executable.hlo_modules()[0]
            if module.name not in names:
                continue
            scopes, inferred = instruction_scopes(module.to_string())
            identity = executable.fingerprint
        except Exception:  # noqa: BLE001 - an executable that shows no text
            continue
        tracing.record_scopes(
            module.name, identity.hex() if isinstance(identity, bytes)
            else str(identity), scopes, inferred)
        kept, instructions = kept + 1, instructions + len(scopes)
    tracing.emit("program.scopes", start=start,
                 duration=time.time() - start,
                 attrs={"programs": len(names), "executables": kept,
                        "instructions": instructions})
    return kept
