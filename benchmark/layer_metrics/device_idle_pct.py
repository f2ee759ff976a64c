"""1 - union of device-op intervals over the traced window, on the busiest device."""

NAME = "device_idle_pct"
UNIT = "%"


def read(ctx):
    return 100.0 * ctx["trace"].idle_share() if ctx["trace"] is not None else None
