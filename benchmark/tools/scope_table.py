"""Device time of a traced step by the program's phases and by the model's blocks, each row
with its longest ops under the compiler's names: who owns ``convert_reduce_fusion.8``.

    python3 benchmark/tools/scope_table.py <file.xplane.pb> <step.hlo.txt>

A block's row is the first two components of its module path (``layer3_0/conv2``,
``block7/attn``). Times are per step: the median over the traced steps of the union of the
row's op intervals, as the per-layer metrics take them; an op's own time is its mean per step.
"""

from __future__ import annotations

import collections
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import scopes, xplane  # noqa: E402


DEPTH = 2  # components of a block's module path that make a row


def rows(trace, scope_map: dict) -> list[dict]:
    """One row a phase, then one a (phase, block), each with ``ms`` and its three longest ops."""
    device = trace.busiest()
    steps = trace.step_events(device)
    op_ns: collections.Counter = collections.Counter()
    for name, s, e in trace.devices[device][xplane.OPS_LINE]:
        if any(s >= lo and e <= hi for _, lo, hi in steps):
            op_ns[xplane.op_key(name)] += e - s

    def row_of(op: str) -> tuple[str, str]:
        phase, block = scope_map.get(op, scopes.NO_SCOPE)
        return phase, "/".join(block.split("/")[:DEPTH])

    members: dict[tuple[str, str], list[str]] = {}
    for op in op_ns:
        members.setdefault(row_of(op), []).append(op)
    out = []
    for phase in sorted({phase for phase, _ in members}):
        out.append(_row(trace, phase, "", [op for key, ops in members.items() if key[0] == phase for op in ops],
                        op_ns, len(steps)))
    for (phase, block), ops in sorted(members.items()):
        if block:
            out.append(_row(trace, phase, block, ops, op_ns, len(steps)))
    return out


def _row(trace, phase: str, block: str, ops: list[str], op_ns: dict, steps: int) -> dict:
    kept = set(ops)
    longest = sorted(ops, key=lambda op: -op_ns[op])[:3]
    return {"phase": phase, "block": block, "ms": scopes.ms_per_step(trace, kept.__contains__),
            "ops": len(ops), "longest": [[op, op_ns[op] / steps / 1e6] for op in longest]}


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 2:
        print(__doc__, file=sys.stderr)
        return 2
    trace = xplane.load(argv[0])
    with open(argv[1]) as f:
        scope_map = scopes.scope_of(f.read())
    print(f"step_device_ms {trace.step_device_ms():.3f} over {len(trace.step_events(trace.busiest()))} steps")
    print(f"{'phase':10s} {'block':28s} {'ms/step':>9s} {'ops':>5s}  longest ops (ms/step)")
    for r in rows(trace, scope_map):
        longest = ", ".join(f"{op} {ms:.3f}" for op, ms in r["longest"])
        print(f"{r['phase']:10s} {r['block']:28s} {r['ms'] or 0.0:9.3f} {r['ops']:5d}  {longest}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
