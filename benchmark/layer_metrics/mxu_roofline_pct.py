"""Least time the chip could take for one step's convolution/matmul work (per pass the larger of
FLOPs over peak and least bytes over peak bandwidth, tensors a fused implementation need not write
left out) over the traced time of the ops that do it: the XLA ops that hold a convolution or dot,
and the kernels whose ``kernels/<name>.py`` says ``matrix``. The same work over whatever implements it."""

from benchmark import scopes

NAME = "mxu_roofline_pct"
UNIT = "%"


def read(ctx):
    trace, peaks = ctx["trace"], ctx.get("peaks")
    if trace is None or peaks is None:
        return None
    classes, kernels = ctx["classes"], ctx["kernels"]
    matrix = lambda op: classes.get(op) == "mxu" or kernels.get(op, {}).get("matrix", False)
    took = scopes.ms_per_step(trace, matrix)
    if not took:
        return None
    least = ctx["roofline"].mxu_min_seconds_per_step(ctx["layers"], ctx["batch_per_chip"], peaks)
    return 100.0 * least * 1000.0 / took
