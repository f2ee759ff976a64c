"""Median duration of the program's ``dtpu.h2d_transfer`` spans (``to_device(batch)`` on the
prefetch thread, one a step) inside the steady span."""

from benchmark import scopes

NAME = "h2d_ms_per_step"
UNIT = "ms"


def read(ctx):
    return scopes.read_span_ms(ctx, "dtpu.h2d_transfer")
