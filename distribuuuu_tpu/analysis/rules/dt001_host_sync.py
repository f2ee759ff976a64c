"""DT001: host sync inside a step/epoch loop.

The single most expensive invisible bug in a JAX training loop: an
``.item()``, ``float()``/``int()`` on a device value, ``np.asarray``, or an
unguarded ``jax.device_get`` executed *every iteration* stalls the
accelerator on dispatch latency once per step. The reference torch code did
exactly this — per-iteration ``.item()`` metric syncs — and this repo's
rebuild exists to not: see the docstring of ``distribuuuu_tpu/metrics.py``
(the motivating example for this rule), where ``topk_correct`` returns
on-device counters precisely so the trainer only materializes them every
PRINT_FREQ iterations.

Flagged, inside any loop that drives device steps (a dispatch call in the
body, or a ``for`` over a loader/prefetch iterator):

* ``x.item()``;
* ``float(e)`` / ``int(e)`` where ``e`` references a value bound from a
  dispatch call (device-resident);
* ``np.asarray(e)`` / ``np.array(e)`` on such a value;
* ``jax.device_get(...)`` / ``block_until_ready(...)`` whose result is
  *consumed* (assigned or nested in an expression).

Whitelisted sync points (not flagged):

* anything under a periodic-boundary ``if`` — a modulo test
  (``it % PRINT_FREQ == 0``) or a last-iteration test
  (``it == len(loader) - 1``): that is the PRINT_FREQ batching idiom;
* a *bare statement* ``jax.device_get(x)`` / ``block_until_ready(x)``
  whose value is discarded: a deliberate, self-documenting barrier (the
  benchmark gating idiom of a timed loop);
* values already fetched via ``device_get`` (host-bound names).
"""

from __future__ import annotations

import ast

from distribuuuu_tpu.analysis.rules.common import (
    SYNC_FUNCS,
    ModuleModel,
    RawFinding,
    call_name,
    dotted,
)

CODE = "DT001"
AUTOFIXABLE = False

_NP_CONVERTERS = {"np.asarray", "np.array", "numpy.asarray", "numpy.array"}


def _finding(node: ast.AST, message: str) -> RawFinding:
    return RawFinding(node.lineno, node.col_offset, CODE, message)


def check(tree: ast.AST, model: ModuleModel, ctx) -> list[RawFinding]:
    findings: list[RawFinding] = []
    step_loops = [
        n
        for n in model.nodes
        if isinstance(n, (ast.For, ast.While)) and model.is_step_loop(n)
    ]
    seen: set[tuple[int, int]] = set()
    for loop in step_loops:
        for node in ast.walk(loop):
            if not isinstance(node, ast.Call):
                continue
            key = (node.lineno, node.col_offset)
            if key in seen:
                continue
            f = _check_call(node, model)
            if f is not None:
                seen.add(key)
                findings.append(f)
    return findings


def _check_call(node: ast.Call, model: ModuleModel) -> RawFinding | None:
    func = node.func
    # x.item()
    if isinstance(func, ast.Attribute) and func.attr == "item" and not node.args:
        if model.in_sync_region(node):
            return None
        return _finding(
            node,
            "`.item()` in a step loop forces a device->host sync every "
            "iteration; accumulate on device and fetch at a PRINT_FREQ "
            "boundary (see distribuuuu_tpu/metrics.py)",
        )
    name = call_name(node)
    dname = dotted(func)
    # float()/int() on device values
    if isinstance(func, ast.Name) and func.id in {"float", "int"} and node.args:
        if model.references_device_value(node.args[0]) and not model.in_sync_region(node):
            return _finding(
                node,
                f"`{func.id}()` on a device value in a step loop syncs every "
                "iteration; fetch the window once at a boundary instead",
            )
        return None
    # np.asarray / np.array on device values
    if dname in _NP_CONVERTERS and node.args:
        if model.references_device_value(node.args[0]) and not model.in_sync_region(node):
            return _finding(
                node,
                f"`{dname}()` on a device value in a step loop is a hidden "
                "device->host transfer; use jax.device_get at a boundary",
            )
        return None
    # consumed device_get / block_until_ready
    if name in SYNC_FUNCS:
        if model.in_sync_region(node):
            return None
        stmt = model.parents.enclosing_statement(node)
        if isinstance(stmt, ast.Expr) and stmt.value is node:
            return None  # bare barrier statement: deliberate gate
        return _finding(
            node,
            f"`{name}` consumed inside a step loop syncs every iteration; "
            "move the fetch to a periodic boundary or discard the result "
            "(bare-statement barrier)",
        )
    return None
