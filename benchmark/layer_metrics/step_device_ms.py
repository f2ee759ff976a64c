"""Union of device-op intervals inside one step's module event, median over the traced steps."""

NAME = "step_device_ms"
UNIT = "ms"


def read(ctx):
    return ctx["trace"].step_device_ms() if ctx["trace"] is not None else None
