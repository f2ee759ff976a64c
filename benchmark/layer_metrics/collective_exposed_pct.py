"""Share of the traced window, on the busiest device, in which a collective runs and no compute op does.
Finds nothing to read where the trace holds no collective (one chip)."""

NAME = "collective_exposed_pct"
UNIT = "%"


def read(ctx):
    trace = ctx["trace"]
    if trace is None:
        return None
    device = trace.busiest()
    if not trace.busy_ns(device, ctx["classes"], only="collective"):
        return None
    lo, hi = trace.window()
    return 100.0 * trace.exposed_collective_ns(ctx["classes"], device) / (hi - lo)
