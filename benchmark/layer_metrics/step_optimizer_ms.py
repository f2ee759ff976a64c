"""Device time a step spends in ops of the program's ``dtpu.optimizer`` scope that hold no
convolution or dot (``tx.update`` and ``apply_updates_with_lr``), median over the traced steps."""

from benchmark import scopes

NAME = "step_optimizer_ms"
UNIT = "ms"


def read(ctx):
    return scopes.read_phase_ms(ctx, ("optimizer",))
