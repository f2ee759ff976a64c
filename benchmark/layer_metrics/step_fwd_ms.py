"""Device time a step spends in the model's forward (ops whose ``op_name`` holds ``jvp(`` and no
``transpose(``; the loss outside the module, ``dtpu.loss``, with them), median over the traced steps."""

from benchmark import scopes

NAME = "step_fwd_ms"
UNIT = "ms"


def read(ctx):
    return scopes.read_phase_ms(ctx, ("fwd",))
