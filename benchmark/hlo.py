"""Classify the ops of a compiled step from its own HLO text.

``compiled.as_text()`` names every instruction the device trace will show
(``%fusion.12 = ... fusion(...), calls=%fused_computation.12``). An op's class
is decided by what it *contains*, read from the called computations, never by
a substring of its name:

- ``collective``: the instruction is, or contains, an all-reduce, all-gather,
  reduce-scatter, all-to-all or collective-permute (async halves included);
- ``mxu``: it is, or contains, a ``convolution`` or a ``dot``;
- ``vector``: everything else that runs on the device.
"""

from __future__ import annotations

import re

COLLECTIVE_OPCODES = frozenset({
    "all-reduce", "all-reduce-start", "all-reduce-done",
    "all-gather", "all-gather-start", "all-gather-done",
    "reduce-scatter", "all-to-all", "collective-permute",
    "collective-permute-start", "collective-permute-done", "collective-broadcast",
})
MXU_OPCODES = frozenset({"convolution", "dot"})
CLASSES = ("mxu", "vector", "collective")

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->\s*.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_CALLEE = re.compile(r"(?:calls|to_apply|body|condition|branch_computations)=\{?%?([\w.\-]+(?:,\s*%?[\w.\-]+)*)\}?")
_OPCODE = re.compile(r"\s*([a-z][\w\-]*)\(")


def _opcode(rest: str) -> str | None:
    """Opcode of an instruction, given the text after ``name = ``."""
    if rest.startswith("("):  # tuple type: skip its balanced parentheses
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                rest = rest[i + 1:]
                break
    else:  # array type with layout: no spaces inside
        _, _, rest = rest.partition(" ")
    m = _OPCODE.match(rest)
    return m.group(1) if m else None


def parse(text: str) -> dict[str, tuple[str, tuple[str, ...], str]]:
    """instruction name -> (opcode, called computations, owning computation)."""
    instructions: dict[str, tuple[str, tuple[str, ...], str]] = {}
    current = None
    for line in text.splitlines():
        if not line.startswith(" "):
            m = _COMPUTATION.match(line)
            current = m.group(1) if m else (None if line.startswith("}") else current)
            continue
        if current is None:
            continue
        m = _INSTRUCTION.match(line)
        if not m:
            continue
        opcode = _opcode(m.group(2))
        if opcode is None:
            continue
        callees: list[str] = []
        for cm in _CALLEE.finditer(m.group(2)):
            callees += [c.strip().lstrip("%") for c in cm.group(1).split(",")]
        instructions[m.group(1)] = (opcode, tuple(callees), current)
    return instructions


def classify(text: str) -> dict[str, str]:
    """instruction name -> ``mxu`` | ``vector`` | ``collective``."""
    instructions = parse(text)
    by_computation: dict[str, list[str]] = {}
    for name, (_, _, owner) in instructions.items():
        by_computation.setdefault(owner, []).append(name)
    memo: dict[str, frozenset[str]] = {}

    def contents(name: str) -> frozenset[str]:
        """Opcodes of an instruction and of everything it calls."""
        if name in memo:
            return memo[name]
        memo[name] = frozenset()  # guards a cycle, which HLO does not have
        opcode, callees, _ = instructions[name]
        found = {opcode}
        for comp in callees:
            for inner in by_computation.get(comp, ()):
                found |= contents(inner)
        memo[name] = frozenset(found)
        return memo[name]

    classes = {}
    for name in instructions:
        ops = contents(name)
        if ops & COLLECTIVE_OPCODES:
            classes[name] = "collective"
        elif ops & MXU_OPCODES:
            classes[name] = "mxu"
        else:
            classes[name] = "vector"
    return classes
