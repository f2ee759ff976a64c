"""Median duration of the program's ``dtpu.dispatch`` spans (the ``train_step`` call alone on the loop's
thread: the host's cost of launching one step) inside the steady span."""

from benchmark import scopes

NAME = "dispatch_ms_per_step"
UNIT = "ms"


def read(ctx):
    return scopes.read_span_ms(ctx, "dtpu.dispatch")
