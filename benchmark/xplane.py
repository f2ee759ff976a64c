"""Reduce a profiler trace (``.xplane.pb``) to the numbers the layer metrics read.

What the hand-read trace of this chip showed (``PERF.md`` section 3): device
planes are named ``/device:TPU:<n>``; their line ``XLA Ops`` holds one event
per executed HLO instruction, named by the instruction (``fusion.12``), and
their line ``XLA Modules`` one event per executed program, named
``jit_step(<fingerprint>)``. Host threads are the lines of ``/host:CPU``.

Everything here works on intervals ``(start_ns, end_ns)``. Busy time is the
*union* of op intervals, never the sum of durations: ops on different cores
or queues of one device overlap, and a sum would count that time twice.
"""

from __future__ import annotations

import glob
import os
import statistics

DEVICE_PLANE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE_PREFIX = "/host:"
STEADY_SPAN = "bench.trace.steady"  # the harness's own span: the traced window once the profiler runs
OWN_SPANS = (":" + STEADY_SPAN, ":bench.train_epoch")


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path_or_bytes) -> "Trace":
    from jax.profiler import ProfileData

    if isinstance(path_or_bytes, (bytes, bytearray)):
        data = ProfileData.from_serialized_xspace(bytes(path_or_bytes))
    else:
        data = ProfileData.from_file(path_or_bytes)
    devices: dict[str, dict[str, list]] = {}
    host: list[tuple[str, int, int]] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PLANE_PREFIX):
            lines = devices.setdefault(plane.name, {})
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    lines[line.name] = [
                        (ev.name, int(ev.start_ns), int(ev.start_ns + ev.duration_ns))
                        for ev in line.events
                    ]
        elif plane.name.startswith(HOST_PLANE_PREFIX):
            for line in plane.lines:
                for ev in line.events:
                    if ev.duration_ns > 0:
                        host.append((f"{line.name}:{ev.name}", int(ev.start_ns), int(ev.start_ns + ev.duration_ns)))
    steady = [(s, e) for name, s, e in host if name.endswith(":" + STEADY_SPAN)]
    return Trace(devices, host, steady[0] if steady else None)


def op_key(event_name: str) -> str:
    """The HLO instruction an ops-line event stands for (``%fusion.1 = ...`` or ``fusion.1``)."""
    return event_name.split(" ", 1)[0].lstrip("%")


def union(intervals) -> list[tuple[int, int]]:
    """Merge intervals; the result is sorted and disjoint."""
    merged: list[list[int]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return [(s, e) for s, e in merged]


def length(disjoint) -> int:
    return sum(e - s for s, e in disjoint)


def clip(intervals, lo: int, hi: int):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def subtract(a, b) -> list[tuple[int, int]]:
    """Parts of the disjoint, sorted intervals ``a`` that no interval of ``b`` covers."""
    out = []
    b = list(b)
    j = 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def whole_trace_facts(devices: dict, steady) -> dict:
    """What the profiler recorded around the span that is read, for the record beside the result:
    the op events of the fullest device, the seconds they cover, and the longest stretch with no op
    and where it began against the span's start. A device that stands still once its tracer is full
    shows here, after the span, and not in the metrics."""
    ops = max((lines.get(OPS_LINE, []) for lines in devices.values()), key=len, default=[])
    if not ops:
        return {}
    busy = union((s, e) for _, s, e in ops)
    lo = steady[0] if steady is not None else busy[0][0]
    gap_s, gap_at = max(((b[0] - a[1], a[1]) for a, b in zip(busy, busy[1:])), default=(0, lo))
    return {"op_events": len(ops), "recorded_s": (busy[-1][1] - busy[0][0]) / 1e9,
            "events_before_span": sum(1 for _, s, _e in ops if s < lo),
            "longest_gap_s": gap_s / 1e9, "longest_gap_at_s": (gap_at - lo) / 1e9}


class Trace:
    """Device op intervals of one traced window, and the host's spans."""

    def __init__(self, devices: dict, host: list, steady: tuple[int, int] | None = None):
        """``steady``: where given, only events that lie wholly inside it are read, and it is the window."""
        self.whole = whole_trace_facts(devices, steady)
        if steady is not None:
            lo, hi = steady
            devices = {name: {line: [ev for ev in events if ev[1] >= lo and ev[2] <= hi]
                              for line, events in lines.items()} for name, lines in devices.items()}
        self.devices = {name: lines for name, lines in devices.items() if lines.get(OPS_LINE)}
        self.host = host
        self.steady = steady

    # -- the window ---------------------------------------------------------

    def window(self) -> tuple[int, int]:
        """The steady span where the trace has one, else from the first to the last device op."""
        if self.steady is not None:
            return self.steady
        starts = [ev[1] for lines in self.devices.values() for ev in lines[OPS_LINE]]
        ends = [ev[2] for lines in self.devices.values() for ev in lines[OPS_LINE]]
        if not starts:
            raise ValueError("trace holds no device op")
        return min(starts), max(ends)

    def busy_ns(self, device: str, classes: dict | None = None, only: str | None = None) -> int:
        ops = self.devices[device][OPS_LINE]
        if only is not None:
            ops = [ev for ev in ops if classes.get(op_key(ev[0]), "vector") == only]
        return length(union((s, e) for _, s, e in ops))

    def busiest(self) -> str:
        return max(self.devices, key=self.busy_ns)

    def busy_mean_s(self) -> float:
        return statistics.fmean(self.busy_ns(d) for d in self.devices) / 1e9

    def idle_share(self, device: str | None = None) -> float:
        lo, hi = self.window()
        device = device or self.busiest()
        return 1.0 - self.busy_ns(device) / (hi - lo)

    # -- steps --------------------------------------------------------------

    def step_events(self, device: str, module_prefix: str = "jit_step"):
        """Executions of the train step's program, the trace's edge ones left out.

        The step is the module whose name starts with ``module_prefix``; where
        the program renames it, the module that fills most device time stands in.
        """
        modules = self.devices[device].get(MODULES_LINE, [])
        named = [ev for ev in modules if ev[0].startswith(module_prefix)]
        if not named and modules:
            total: dict[str, int] = {}
            for name, s, e in modules:
                total[name] = total.get(name, 0) + e - s
            top = max(total, key=total.get)
            named = [ev for ev in modules if ev[0] == top]
        named = sorted(named, key=lambda ev: ev[1])
        # the first and the last may be cut by the trace's own edges
        return named[1:-1] if len(named) >= 4 else named

    def step_device_ms(self, device: str | None = None) -> float | None:
        """Median over steps of the union of op intervals inside the step's module event."""
        device = device or self.busiest()
        steps = self.step_events(device)
        if not steps:
            return None
        ops = union((s, e) for _, s, e in self.devices[device][OPS_LINE])
        return statistics.median(length(clip(ops, s, e)) for _, s, e in steps) / 1e6

    def step_period_ms(self, device: str | None = None) -> float | None:
        """Wall time per step inside the trace: first step's start to last step's start."""
        device = device or self.busiest()
        steps = self.step_events(device)
        if len(steps) < 2:
            return None
        return (steps[-1][1] - steps[0][1]) / (len(steps) - 1) / 1e6

    # -- classes ------------------------------------------------------------

    def class_ns(self, classes: dict, device: str | None = None) -> dict[str, int]:
        """Union time of each op class on one device (classes may overlap each other)."""
        device = device or self.busiest()
        return {c: self.busy_ns(device, classes, only=c) for c in ("mxu", "vector", "collective")}

    def exposed_collective_ns(self, classes: dict, device: str | None = None) -> int:
        """Time in which a collective runs and no compute op does."""
        device = device or self.busiest()
        ops = self.devices[device][OPS_LINE]
        coll = union((s, e) for n, s, e in ops if classes.get(op_key(n), "vector") == "collective")
        comp = union((s, e) for n, s, e in ops if classes.get(op_key(n), "vector") != "collective")
        return length(subtract(coll, comp))

    # -- breakdown ----------------------------------------------------------

    def top_ops(self, n: int = 10, device: str | None = None) -> list[list]:
        device = device or self.busiest()
        total: dict[str, int] = {}
        for name, s, e in self.devices[device][OPS_LINE]:
            key = op_key(name)
            total[key] = total.get(key, 0) + e - s
        ranked = sorted(total.items(), key=lambda kv: -kv[1])[:n]
        return [[name, ns / 1e9] for name, ns in ranked]

    def idle_gaps(self, n: int = 10, device: str | None = None) -> list[list]:
        """Longest idle gaps of one device, each named by the host span that covers most of it."""
        device = device or self.busiest()
        lo, hi = self.window()
        busy = union((s, e) for _, s, e in self.devices[device][OPS_LINE])
        gaps = sorted(subtract([(lo, hi)], busy), key=lambda g: g[0] - g[1])[:n]
        out = []
        for s, e in gaps:
            # the innermost span says most: among the spans that cover at
            # least half of the gap the shortest, else the largest cover
            best, best_key = "host: no span", (0, 0)
            for name, hs, he in self.host:
                cover = min(e, he) - max(s, hs)
                if cover <= 0 or name.endswith(OWN_SPANS):  # the harness's own spans say nothing
                    continue
                key = (1, hs - he) if 2 * cover >= e - s else (0, cover)
                if key > best_key:
                    best, best_key = name, key
            out.append([f"{best} @+{(s - lo) / 1e9:.2f}s", (e - s) / 1e9])
        return out
