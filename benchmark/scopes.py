"""Attribute the compiled step's instructions, and the trace's events, to the program's own phases.

The executable's metadata already says where an instruction came from: flax writes module paths
into every ``op_name`` (``jit(step_train)/jvp(ResNet)/layer3_0/bn3/mul`` forward,
``.../transpose(jvp(ResNet))/layer3_1/conv3/conv_general_dilated`` backward), and the program
names what follows the gradient with ``jax.named_scope`` (``dtpu.grad_sync``, ``dtpu.optimizer``,
``dtpu.guard``, ``dtpu.metrics``; the loss outside the module is ``dtpu.loss``). Its host spans
(``dtpu.dispatch``, ``dtpu.h2d_transfer`` and kin) are in the trace beside the harness's own.

A helper, and outside ``layer_metrics/`` because every file there is loaded as a metric. Every
function returns nothing, and raises nothing, where the program has no such scope or span.
"""

from __future__ import annotations

import functools
import os
import re
import statistics

from benchmark import hlo, xplane

STEP_HLO = "step.hlo.txt"  # the compiled step's text, written by the harness into the run's out_dir
SCOPE_PREFIX = "dtpu."
STEP_PHASES = ("grad_sync", "optimizer", "guard", "metrics")  # the program's scopes after the gradient
TAIL = ("grad_sync", "guard", "metrics")
UNSCOPED = "unscoped"
NO_SCOPE = (UNSCOPED, "")

_ENTRY = re.compile(r"^ENTRY\s+%?([\w.\-]+)", re.MULTILINE)
_OP_NAME = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?\bmetadata=\{[^}]*?\bop_name=\"([^\"]*)\"", re.MULTILINE)


def step_hlo_text(ctx) -> str | None:
    """The compiled step's text as the harness wrote it, found through the program's own journal."""
    for record in ctx["journal"]:
        if record["kind"] == "run_start" and record.get("out_dir"):
            path = os.path.join(record["out_dir"], STEP_HLO)
            if os.path.isfile(path):
                with open(path) as f:
                    return f.read()
    return None


def scope_of_name(op_name: str | None) -> tuple[str, str]:
    """``(phase, block)`` of one ``op_name``.

    ``phase``: ``bwd`` (a ``transpose(`` component), ``fwd`` (a ``jvp(`` component and no
    ``transpose(``), one of `STEP_PHASES` (a bare ``dtpu.<name>`` component), else ``unscoped``.
    ``block``: the module path below the differentiated function (``layer3_0/conv2``,
    ``block7/attn/qkv``), that function's own name for what it runs outside any module
    (``ResNet``, ``dtpu.loss``), or the scope's name.
    """
    if not op_name:
        return NO_SCOPE
    parts = op_name.split("/")
    for i, part in enumerate(parts):
        if "jvp(" in part:
            phase = "bwd" if any("transpose(" in p for p in parts) else "fwd"
            function = part[part.index("jvp(") + 4:].rstrip(")")
            path = [p for p in parts[i + 1:-1] if not p.startswith("jit(")]  # jit(_pad), jit(log_softmax)
            return phase, "/".join(path) or function
    for part in parts:
        if part.startswith(SCOPE_PREFIX) and part[len(SCOPE_PREFIX):] in STEP_PHASES:
            return part[len(SCOPE_PREFIX):], part
    return NO_SCOPE


def op_names(hlo_text: str) -> dict[str, str]:
    """instruction name -> its ``op_name``, for every instruction of every computation that has one."""
    return dict(_OP_NAME.findall(hlo_text))


@functools.lru_cache(maxsize=1)
def scope_of(hlo_text: str) -> dict[str, tuple[str, str]]:
    """instruction name -> ``(phase, block)`` for the entry computation's instructions.

    An instruction that calls computations (a fusion) takes the scope of the convolution or dot it
    holds where it holds one, else its own, which is its root's: XLA fuses the optimizer's
    multiply-add into the weight-gradient convolution's output, and by the root alone that whole
    convolution would read as ``optimizer``. Kept for the run: five readers ask for the same text.
    """
    entry = _ENTRY.search(hlo_text)
    if entry is None:
        return {}
    instructions = hlo.parse(hlo_text)
    names = op_names(hlo_text)
    by_computation: dict[str, list[str]] = {}
    for name, (_, _, owner) in instructions.items():
        by_computation.setdefault(owner, []).append(name)

    def held(name: str, seen: set) -> list[str]:
        """Instructions of everything ``name`` calls, in the text's order."""
        out = []
        for computation in instructions[name][1]:
            if computation not in seen:
                seen.add(computation)
                for inner in by_computation.get(computation, ()):
                    out.append(inner)
                    out += held(inner, seen)
        return out

    scopes = {}
    for name in by_computation.get(entry.group(1), ()):
        inner = held(name, set())
        mxu = next((i for i in inner if instructions[i][0] in hlo.MXU_OPCODES), None)
        scope = scope_of_name(names.get(mxu)) if mxu else NO_SCOPE
        scopes[name] = scope if scope != NO_SCOPE else scope_of_name(names.get(name))
    return scopes


def ms_per_step(trace, keep, device: str | None = None) -> float | None:
    """Median over the step's module events of the union time of the ops whose instruction ``keep``
    accepts (a class of ops, a phase, a block); nothing where no such op ran in a step."""
    device = device or trace.busiest()
    steps = trace.step_events(device)
    ops = xplane.union((s, e) for n, s, e in trace.devices[device][xplane.OPS_LINE] if keep(xplane.op_key(n)))
    per_step = [xplane.length(xplane.clip(ops, s, e)) for _, s, e in steps]
    if not any(per_step):
        return None
    return statistics.median(per_step) / 1e6


def phase_ms_per_step(trace, scopes: dict, phases, device: str | None = None) -> float | None:
    """`ms_per_step` of the ops that ``scopes`` puts into one of ``phases``."""
    return ms_per_step(trace, lambda op: scopes.get(op, NO_SCOPE)[0] in phases, device)


def host_span_ms(trace, name: str) -> list[float]:
    """Durations of the host events named ``<thread>:<name>`` that lie inside ``trace.window()``."""
    lo, hi = trace.window()
    return [(e - s) / 1e6 for event, s, e in trace.host if event.endswith(":" + name) and s >= lo and e <= hi]


def read_phase_ms(ctx, phases) -> float | None:
    """What a ``step_<phase>_ms`` metric reads: ``phase_ms_per_step`` of this run's trace and step."""
    trace = ctx["trace"]
    text = step_hlo_text(ctx) if trace is not None else None
    if text is None:
        return None
    return phase_ms_per_step(trace, scope_of(text), phases)


def read_span_ms(ctx, name: str) -> float | None:
    """What a ``<span>_ms_per_step`` metric reads: the median duration of the program's span."""
    if ctx["trace"] is None:
        return None
    durations = host_span_ms(ctx["trace"], name)
    return statistics.median(durations) if durations else None
