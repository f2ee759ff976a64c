"""Pallas interpret mode: asked for, never inferred from the platform.

The kernels in this package compile through Mosaic by default, everywhere.
A process that has no TPU and still wants to run them (the CPU test suite,
`scripts/cpu_mesh_run.py`, a `chip_smoke.py --rehearse-cpu` rehearsal) says
so once with `set_pallas_interpret(True)`; kernels traced from inside flax
modules, where no caller can thread an ``interpret=`` argument through,
read it at trace time. Code on the trainer's path never calls the setter:
on a machine whose chip is missing a fused route fails in the compiler
instead of quietly running the interpreter on the host.
"""

from __future__ import annotations

_INTERPRET = False


def set_pallas_interpret(enabled: bool) -> bool:
    """Select the Pallas interpreter for kernels that were not handed an
    explicit ``interpret=``; returns the previous setting."""
    global _INTERPRET
    prev, _INTERPRET = _INTERPRET, bool(enabled)
    return prev


def pallas_interpret() -> bool:
    return _INTERPRET
