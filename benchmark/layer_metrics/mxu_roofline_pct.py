"""Least time the chip could take for one step's convolution/matmul work (per pass the larger of
FLOPs over peak and least bytes over peak bandwidth) over the traced time of the ops that hold it."""

NAME = "mxu_roofline_pct"
UNIT = "%"


def read(ctx):
    trace, peaks = ctx["trace"], ctx.get("peaks")
    if trace is None or peaks is None:
        return None
    took = trace.class_ms_per_step(ctx["classes"], "mxu")
    if not took:
        return None
    least = ctx["roofline"].mxu_min_seconds_per_step(ctx["layers"], ctx["batch_per_chip"], peaks)
    return 100.0 * least * 1000.0 / took
