"""Device time a step spends in the backward pass (ops whose ``op_name`` holds ``transpose(``;
weight-gradient convolutions with the optimizer fused into their output count here), median over the traced steps."""

from benchmark import scopes

NAME = "step_bwd_ms"
UNIT = "ms"


def read(ctx):
    return scopes.read_phase_ms(ctx, ("bwd",))
