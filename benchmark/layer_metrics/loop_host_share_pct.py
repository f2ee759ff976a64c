"""Share of a step's wall time in which the device did not run the step: 1 - device busy per step / wall per step (trace)."""

NAME = "loop_host_share_pct"
UNIT = "%"


def read(ctx):
    trace = ctx["trace"]
    if trace is None:
        return None
    busy, period = trace.step_device_ms(), trace.step_period_ms()
    if busy is None or period is None:
        return None
    return 100.0 * (1.0 - busy / period)
