"""Device time a step spends in the program's ``dtpu.grad_sync``, ``dtpu.guard`` and ``dtpu.metrics``
scopes (gradient mean, non-finite guard, metric sums), median over the traced steps."""

from benchmark import scopes

NAME = "step_tail_ms"
UNIT = "ms"


def read(ctx):
    return scopes.read_phase_ms(ctx, scopes.TAIL)
