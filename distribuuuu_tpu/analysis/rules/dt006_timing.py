"""DT006: untimed device work — wall-clock around dispatch without a sync.

JAX dispatch is asynchronous: ``t0 = time.perf_counter(); step(...); dt =
time.perf_counter() - t0`` measures *enqueue* latency, not execution — on
one transport in this repo's history it over-reported throughput ~100x.
The honest pattern closes the timed span with a real
fetch: ``jax.device_get`` on a value that depends on the work (or
``block_until_ready``) before the second timestamp — see
``bench._timed_cadence_loop`` for the canonical gated loop.

Detection, per function scope: a timestamp binding (``t0 = time.time() /
perf_counter() / monotonic()``), a closing elapsed expression
(``time.x() - t0``), and between the two (by source position) at least one
dispatch call (jit-bound or step-named) with **no** sync anywhere in the
span — sync being ``device_get``, ``block_until_ready``, ``.item()``, or an
``np.asarray`` of a device value. Spans with no dispatch (host timing:
data-loader throughput, file I/O) are ignored.
"""

from __future__ import annotations

import ast

from distribuuuu_tpu.analysis.rules.common import (
    ModuleModel,
    RawFinding,
    dotted,
    call_name,
    pos_key,
)

CODE = "DT006"
AUTOFIXABLE = False

_CLOCKS = {
    "time.time",
    "time.perf_counter",
    "time.monotonic",
    "perf_counter",
    "monotonic",
}


def _is_clock_call(node: ast.AST) -> bool:
    return isinstance(node, ast.Call) and (dotted(node.func) in _CLOCKS)


def _is_sync_call(node: ast.Call, model: ModuleModel) -> bool:
    cn = call_name(node) or ""
    if cn in {"device_get", "block_until_ready"}:
        return True
    if isinstance(node.func, ast.Attribute) and node.func.attr == "item":
        return True
    if (dotted(node.func) or "") in {"np.asarray", "np.array", "numpy.asarray"}:
        return model.references_device_value(node)
    return False


def check(tree: ast.AST, model: ModuleModel, ctx) -> list[RawFinding]:
    findings: list[RawFinding] = []
    for scope in model.functions:
        findings.extend(_check_scope(scope, model))
    return findings


def _check_scope(scope: ast.AST, model: ModuleModel) -> list[RawFinding]:
    # timestamp bindings: t0 = time.perf_counter()
    nodes = model.scope_nodes(scope)
    stamps: dict[str, tuple[int, int]] = {}
    for node in nodes:
        if isinstance(node, ast.Assign) and _is_clock_call(node.value):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    stamps[t.id] = pos_key(node)
    if not stamps:
        return []
    # closing expressions: <clock call> - t0
    closes: list[tuple[str, ast.BinOp]] = []
    for node in nodes:
        if (
            isinstance(node, ast.BinOp)
            and isinstance(node.op, ast.Sub)
            and isinstance(node.right, ast.Name)
            and node.right.id in stamps
            and _is_clock_call(node.left)
        ):
            closes.append((node.right.id, node))

    findings: list[RawFinding] = []
    for name, close in closes:
        start = stamps[name]
        end = pos_key(close)
        if end <= start:
            continue  # loop-carried reuse; linear span only
        dispatch = None
        synced = False
        for node in nodes:
            if not isinstance(node, ast.Call):
                continue
            p = pos_key(node)
            if not (start < p <= end):
                continue
            if _is_sync_call(node, model):
                synced = True
            elif model.is_dispatch_call(node):
                dispatch = node
        if dispatch is not None and not synced:
            findings.append(
                RawFinding(
                    close.lineno,
                    close.col_offset,
                    CODE,
                    f"elapsed time over `{call_name(dispatch)}` dispatch without "
                    "a device sync in the span: async dispatch makes this "
                    "measure enqueue latency, not execution — gate the stop "
                    "timestamp on jax.device_get/block_until_ready",
                )
            )
    return findings
