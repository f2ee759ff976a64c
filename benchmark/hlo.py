"""Classify the ops of a compiled step from its own HLO text.

``compiled.as_text()`` names every instruction the device trace will show
(``%fusion.12 = ... fusion(...), calls=%fused_computation.12``). An op's class
is decided by what it *contains*, read from the called computations, never by
a substring of its name:

- ``collective``: the instruction is, or contains, an all-reduce, all-gather,
  reduce-scatter, all-to-all or collective-permute (async halves included);
- ``mxu``: it is, or contains, a ``convolution`` or a ``dot``;
- ``kernel``: it is, or contains, a ``custom-call`` whose target is ``tpu_custom_call``: a
  Mosaic kernel, whose body the text does not show. Its name is the ``name`` the program gave
  its ``pallas_call`` (the component of ``op_name`` before ``/pallas_call``), and what it costs
  is said by ``benchmark/kernels/<name>.py`` from the call's shapes (`kernel_calls`);
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
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'
CLASSES = ("mxu", "kernel", "vector", "collective")

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->\s*.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*)$")
_CALLEE = re.compile(r"(?:calls|to_apply|body|condition|branch_computations)=\{?%?([\w.\-]+(?:,\s*%?[\w.\-]+)*)\}?")
_OPCODE = re.compile(r"\s*([a-z][\w\-]*)\(")
_ARRAY = re.compile(r"\b([a-z]+\d*[a-z0-9]*)\[([\d,]*)\]")
_KERNEL_NAME = re.compile(r'op_name="[^"]*?([^/"]+)/pallas_call[^"]*"')
_OPERANDS = re.compile(r"\bcustom-call\(([^)]*)\)")


def _split_type(rest: str) -> tuple[str, str]:
    """The result type of an instruction and what follows it, given the text after ``name = ``."""
    if rest.startswith("("):  # tuple type: up to its balancing parenthesis
        depth = 0
        for i, ch in enumerate(rest):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                return rest[: i + 1], rest[i + 1:]
        return rest, ""
    head, _, tail = rest.partition(" ")  # array type with layout: no spaces inside
    return head, tail


def _opcode(rest: str) -> str | None:
    """Opcode of an instruction, given the text after ``name = ``."""
    m = _OPCODE.match(_split_type(rest)[1])
    return m.group(1) if m else None


def arrays(type_text: str) -> list[tuple[str, tuple[int, ...]]]:
    """``(dtype, shape)`` of every array in a type's text (``bf16[128,197,768]{2,1,0:T(8,128)(2,1)}``, or a tuple of such)."""
    return [(dtype, tuple(int(d) for d in dims.split(",") if d)) for dtype, dims in _ARRAY.findall(type_text)]


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


def _kernel_lines(text: str) -> dict[str, str]:
    """instruction name -> the text after ``name = ``, for the lines that name the Mosaic custom-call target."""
    found = {}
    for line in text.splitlines():
        if KERNEL_TARGET in line:
            m = _INSTRUCTION.match(line)
            if m:
                found[m.group(1)] = m.group(2)
    return found


def kernel_calls(text: str) -> dict[str, dict]:
    """instruction name -> ``{"kernel", "operands", "results"}`` for every Mosaic kernel call of the text.

    ``kernel`` is the name the program gave the kernel; ``operands`` and ``results`` are lists of
    ``(dtype, shape)`` as the compiled step holds them: what ``benchmark/kernels/<kernel>.py``'s
    ``cost`` prices the call from. A call that carries no name raises: nothing could price it.
    """
    calls = _kernel_lines(text)
    if not calls:
        return {}
    types = {m.group(1): _split_type(m.group(2))[0]
             for m in map(_INSTRUCTION.match, text.splitlines()) if m}
    out = {}
    for name, rest in calls.items():
        named = _KERNEL_NAME.search(rest)
        if named is None:
            raise ValueError(f"the kernel call {name} carries no op_name ending in <name>/pallas_call: name its pallas_call")
        operands = [o.split()[-1].lstrip("%") for o in _OPERANDS.search(rest).group(1).split(",") if o.strip()]
        out[name] = {"kernel": named.group(1), "results": arrays(types[name]),
                     "operands": [a for o in operands for a in arrays(types[o])]}
    return out


def classify(text: str) -> dict[str, str]:
    """instruction name -> ``mxu`` | ``kernel`` | ``vector`` | ``collective``."""
    instructions = parse(text)
    kernels = set(_kernel_lines(text))
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
        found = {"kernel" if name in kernels else opcode}
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
        elif "kernel" in ops:
            classes[name] = "kernel"
        else:
            classes[name] = "vector"
    return classes
