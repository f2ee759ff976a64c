"""Share of device busy time in ops that hold no convolution, dot, collective or Mosaic kernel (trace + the step's HLO)."""

NAME = "vector_share_pct"
UNIT = "%"


def read(ctx):
    trace = ctx["trace"]
    if trace is None:
        return None
    device = trace.busiest()
    busy = trace.busy_ns(device)
    return 100.0 * trace.busy_ns(device, ctx["classes"], only="vector") / busy if busy else None
