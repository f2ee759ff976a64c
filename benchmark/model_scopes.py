"""Device time under the scopes a program gives the parts of a mixer that module paths cannot tell
apart (``dtpu.ssm_scan``, ``dtpu.moe_route``, ``dtpu.moe_experts``), forward and backward together.

A helper, and outside ``layer_metrics/`` because every file there is loaded as a metric. Unlike
``scopes.scope_of``, which attributes the entry computation's instructions, this reads the
instructions of *every* computation: a model whose repeated layers are one scan runs them inside a
``while``, whose body's ops the trace shows under their own names beside the loop's own event. An
instruction is under a scope if ``dtpu.<scope>`` is a component of its ``op_name``; a fusion takes
the ``op_name`` of the convolution or dot it holds, else its own. Loops, conditionals and calls
are left out: they only span the ops they run, which are counted themselves. Returns nothing, and
raises nothing, where the program has no such scope.
"""

from __future__ import annotations

import functools

from benchmark import hlo, scopes

SPANNING = frozenset({"while", "conditional", "call"})  # ops whose event covers other ops' events


@functools.lru_cache(maxsize=1)
def op_name_of(text: str) -> dict[str, str]:
    """instruction name -> the ``op_name`` that places it, for the ops of every computation that run
    on their own (no loop, conditional or call)."""
    instructions = hlo.parse(text)
    names = scopes.op_names(text)
    by_computation: dict[str, list[str]] = {}
    for name, (_, _, owner) in instructions.items():
        by_computation.setdefault(owner, []).append(name)
    out = {}
    for name, (opcode, callees, _) in instructions.items():
        if opcode in SPANNING:
            continue
        inner = [i for computation in callees for i in by_computation.get(computation, ())]
        mxu = next((i for i in inner if instructions[i][0] in hlo.MXU_OPCODES and names.get(i)), None)
        out[name] = names.get(mxu) if mxu else names.get(name, "")
    return out


def ms_under(ctx, scope: str) -> float | None:
    """Median over the traced steps of the union time of the ops under ``dtpu.<scope>``."""
    trace = ctx["trace"]
    text = scopes.step_hlo_text(ctx) if trace is not None else None
    if text is None:
        return None
    wanted = scopes.SCOPE_PREFIX + scope
    placed = op_name_of(text)
    return scopes.ms_per_step(trace, lambda op: wanted in placed.get(op, "").split("/"))


def window_counter(ctx, name: str) -> list[float]:
    """The measured window's values of one of the program's ``window`` counters."""
    epoch = ctx["window"]["epoch"]
    return [r[name] for r in ctx["journal"] if r["kind"] == "window" and r["epoch"] == epoch and name in r]
