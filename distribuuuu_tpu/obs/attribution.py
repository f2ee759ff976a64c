"""Step-time attribution: the per-op trace folded into roofline buckets.

The share of a step outside the matmuls as standing telemetry: the
profiler's per-op table (`obs/traceparse.py`) is folded into five buckets —

    matmul      convolution / dot / einsum fusions (MXU work)
    vector      everything else on the device tracks (VPU: BN, relu,
                residual adds, optimizer math, transposes)
    collective  all-reduce / all-gather / reduce-scatter / all-to-all /
                collective-permute (ICI)
    infeed      infeed / outfeed stalls counted on device tracks
    host        host-track transfer/infeed work (a LOWER BOUND: only the
                host ops the profiler names as transfers are counted, not
                arbitrary Python time)

— journaled as a typed ``step_attribution`` record beside every ``profile``
record, rendered by ``obs summarize`` as a roofline section, and exported as
``dtpu_attr_*`` gauges.

Classification is by substring on the fusion-category name (the op name
with the ``.N`` instance suffix stripped, `traceparse.summarize_device_ops`
convention). XLA spells these stably across backends ("%fusion" wrappers
keep the root op's name in the category), so a handful of markers covers
the families; anything unrecognized is VPU work by definition of the
residual bucket.
"""

from __future__ import annotations

import json
from typing import Any, Mapping

from distribuuuu_tpu.obs import traceparse

BUCKETS = ("matmul", "vector", "collective", "infeed", "host")

# substring -> bucket, checked in order (first match wins); lowercase
_MARKERS: tuple[tuple[str, str], ...] = (
    ("convolution", "matmul"),
    ("conv", "matmul"),
    ("dot", "matmul"),
    ("matmul", "matmul"),
    ("einsum", "matmul"),
    ("all-reduce", "collective"),
    ("all-gather", "collective"),
    ("reduce-scatter", "collective"),
    ("all-to-all", "collective"),
    ("collective-permute", "collective"),
    ("collective", "collective"),
    ("psum", "collective"),
    ("infeed", "infeed"),
    ("outfeed", "infeed"),
)


def classify_op(name: str) -> str:
    """Bucket for one device-track op/fusion-category name."""
    low = name.lower()
    for marker, bucket in _MARKERS:
        if marker in low:
            return bucket
    return "vector"


def attribute_events(events: list[dict], steps: int) -> dict:
    """Fold raw trace events into per-step bucket milliseconds.

    Returns ``{steps, device_ms_per_step, buckets, matmul_pct, host_ms}``
    with ``buckets`` a ms-per-step dict over `BUCKETS` (host excluded from
    ``device_ms_per_step`` — it overlaps device time, it doesn't extend it).
    A trace with no device tracks (CPU runs) yields ``device_ms_per_step``
    None and zero buckets, mirroring `traceparse.op_table`.
    """
    steps = max(1, int(steps))
    # device tracks: reuse traceparse's pid classification via its category
    # totals (instance suffixes already folded)
    _rows, cats, total, _tracks = traceparse.summarize_device_ops(events, top=10**6)
    buckets = {b: 0.0 for b in BUCKETS}
    for name, dur in cats:
        buckets[classify_op(name)] += dur
    # host-side transfer/infeed work from the host tracks — the cheap,
    # trace-visible slice of host time only (documented lower bound)
    track = {}
    for e in events:
        if e.get("ph") == "M" and e.get("name") == "process_name":
            track[e["pid"]] = e.get("args", {}).get("name", "").lower()
    host_us = 0.0
    for e in events:
        if e.get("ph") != "X" or "dur" not in e:
            continue
        tname = track.get(e.get("pid"), "")
        if "host" not in tname:
            continue
        low = e.get("name", "").lower()
        if "transfer" in low or "infeed" in low or "copy" in low:
            host_us += e["dur"]
    buckets["host"] = round(host_us / 1e3 / steps, 4)
    for b in ("matmul", "vector", "collective", "infeed"):
        buckets[b] = round(buckets[b] / 1e3 / steps, 4)
    device_ms = total / 1e3 / steps if total > 0 else None
    matmul_pct = (
        round(100.0 * buckets["matmul"] / device_ms, 2) if device_ms else None
    )
    return {
        "steps": steps,
        "device_ms_per_step": device_ms,
        "buckets": buckets,
        "matmul_pct": matmul_pct,
        "host_ms": buckets["host"],
    }


def attribute_logdir(logdir: str, steps: int) -> dict:
    """`attribute_events` over the newest trace under ``logdir``; degrades to
    the no-device-tracks shape when the trace is absent/unreadable (the
    profiler window still journals that it ran)."""
    try:
        events = traceparse.load_trace_events(logdir)
    except (OSError, FileNotFoundError, KeyError, json.JSONDecodeError):
        return {
            "steps": max(1, int(steps)),
            "device_ms_per_step": None,
            "buckets": {b: 0.0 for b in BUCKETS},
            "matmul_pct": None,
            "host_ms": 0.0,
        }
    return attribute_events(events, steps)


def attribution_record(
    logdir: str,
    steps: int,
    *,
    gstep: int | None = None,
    trigger: str | None = None,
) -> dict:
    """Journal-ready ``step_attribution`` fields for one profiled window
    (device kind + the peak of `obs/flops.peak_flops_per_device` attached
    when the backend has them, so the roofline section can state MFU context
    inline)."""
    rec = attribute_logdir(logdir, steps)
    rec["logdir"] = str(logdir)
    if gstep is not None:
        rec["gstep"] = int(gstep)
    if trigger is not None:
        rec["trigger"] = str(trigger)
    try:
        import jax

        from distribuuuu_tpu.config import cfg
        from distribuuuu_tpu.obs.flops import peak_flops_per_device

        device = jax.devices()[0]
        rec["device_kind"] = device.device_kind
        peak = peak_flops_per_device(device, cfg.OBS.PEAK_TFLOPS_PER_DEVICE)
        rec["ceiling_tflops"] = None if peak is None else peak / 1e12
    except Exception:
        pass
    return rec


def render_roofline(rec: Mapping[str, Any]) -> list[str]:
    """The ``step_attribution`` record as summarize-style lines (shared by
    ``obs summarize`` and the scripts so the roofline reads the same
    everywhere)."""
    lines: list[str] = []
    dev = rec.get("device_ms_per_step")
    steps = rec.get("steps")
    head = f"  {steps} step(s)"
    if dev is not None:
        head += f", {dev:.2f} ms/step on device"
    if rec.get("device_kind"):
        head += f" [{rec['device_kind']}]"
    lines.append(head)
    buckets = rec.get("buckets") or {}
    if dev:
        for b in BUCKETS:
            ms = float(buckets.get(b, 0.0))
            if b == "host":
                if ms:
                    lines.append(
                        f"    {b:<10} {ms:8.2f} ms/step (host tracks; lower bound)"
                    )
                continue
            lines.append(f"    {b:<10} {ms:8.2f} ms/step ({100.0 * ms / dev:5.1f}%)")
        pct = rec.get("matmul_pct")
        if pct is not None:
            lines.append(
                f"    outside-the-matmuls: {100.0 - float(pct):.1f}% of device time"
            )
    ceiling = rec.get("ceiling_tflops")
    if ceiling:
        lines.append(f"    peak: {float(ceiling):g} TFLOP/s per device")
    return lines
