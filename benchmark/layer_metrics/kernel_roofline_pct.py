"""Share of their roofline that the step's Mosaic kernels reach together: over the kernel calls that ran
inside the traced steps, the sum of each call's least time (the larger of its FLOPs over the chip's peak
and its bytes over peak bandwidth, by ``kernels/<name>.py`` from the call's shapes) over the time they
took. Finds nothing to read where the step holds no kernel."""

from benchmark import xplane

NAME = "kernel_roofline_pct"
UNIT = "%"


def read(ctx):
    trace, peaks, kernels = ctx["trace"], ctx.get("peaks"), ctx["kernels"]
    if trace is None or peaks is None or not kernels:
        return None
    device = trace.busiest()
    steps = trace.step_events(device)
    ran = [(xplane.op_key(n), s, e) for n, s, e in trace.devices[device][xplane.OPS_LINE]
           if xplane.op_key(n) in kernels and any(s >= lo and e <= hi for _, lo, hi in steps)]
    took_ns = xplane.length(xplane.union((s, e) for _, s, e in ran))
    if not took_ns:
        return None
    least_s = sum(ctx["roofline"].kernel_min_seconds(kernels[op], peaks) for op, _, _ in ran)
    return 100.0 * least_s * 1e9 / took_ns
