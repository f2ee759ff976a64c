"""Perfetto/chrome-trace parsing for ``jax.profiler`` exports.

TensorBoard isn't available on headless pods, so the per-op device-time
breakdown is computed directly from the profiler's trace export
(``plugins/profile/<run>/*.trace.json.gz``): aggregate complete ('X') events
on device tracks by op name, fold instance suffixes into fusion categories.
The programmatic profiler windows (`obs/profiler.py`) journal this table.
"""

from __future__ import annotations

import glob
import gzip
import json
import os
from collections import defaultdict


def load_trace_events(logdir: str) -> list[dict]:
    """Trace events of the newest profile run under ``logdir``."""
    paths = sorted(
        glob.glob(os.path.join(logdir, "plugins", "profile", "*", "*.trace.json.gz"))
    )
    if not paths:
        raise FileNotFoundError(f"no trace.json.gz under {logdir}")
    with gzip.open(paths[-1], "rt") as f:
        return json.load(f)["traceEvents"]


def summarize_device_ops(events: list[dict], top: int):
    """Aggregate device-track op time.

    Returns ``(rows, cats, total, tracks)``: the hottest single ops, the
    per-fusion-category totals (instance suffix ``.N`` stripped), the total
    device op time (µs), and the track names seen (for debugging which pids
    were counted).
    """
    # pid -> process (track) name from metadata events
    track = {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            track[e["pid"]] = e.get("args", {}).get("name", "")

    def is_device(pid) -> bool:
        name = track.get(pid, "").lower()
        return ("tpu" in name or "device" in name or "xla ops" in name) and (
            "host" not in name
        )

    by_op = defaultdict(float)
    by_cat = defaultdict(float)
    total = 0.0
    for e in events:
        if e.get("ph") != "X" or not is_device(e.get("pid")) or "dur" not in e:
            continue
        name = e["name"]
        # skip the whole-module envelope and the step-number marker tracks —
        # they overlap the individual op executions and would double-count
        if name.startswith("jit_") or name.isdigit():
            continue
        by_op[name] += e["dur"]
        # category = fusion kind without the ".N" instance suffix
        by_cat[name.split(".", 1)[0]] += e["dur"]
        total += e["dur"]
    rows = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]
    cats = sorted(by_cat.items(), key=lambda kv: -kv[1])[:top]
    return rows, cats, total, sorted(set(track.values()))


def op_table(logdir: str, steps: int, top: int = 20) -> dict:
    """Journal-ready per-op summary of a traced window.

    ``{device_ms_per_step, top_ops: [{op, ms_per_step, pct}, ...]}``; CPU
    traces often carry no device tracks, in which case ``device_ms_per_step``
    is None and ``top_ops`` is empty — the profile record still marks that
    the window ran and where the raw trace lives.
    """
    try:
        events = load_trace_events(logdir)
    except (OSError, FileNotFoundError, KeyError, json.JSONDecodeError):
        return {"device_ms_per_step": None, "top_ops": []}
    rows, _cats, total, _tracks = summarize_device_ops(events, top)
    steps = max(1, steps)
    if total <= 0:
        return {"device_ms_per_step": None, "top_ops": []}
    return {
        "device_ms_per_step": total / 1e3 / steps,
        "top_ops": [
            {
                "op": name if len(name) <= 80 else name[:77] + "...",
                "ms_per_step": round(dur / 1e3 / steps, 4),
                "pct": round(100.0 * dur / total, 2),
            }
            for name, dur in rows
        ],
    }
