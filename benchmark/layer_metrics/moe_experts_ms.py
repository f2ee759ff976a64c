"""Device time a step spends in the ops of the program's ``dtpu.moe_experts`` scope: the grouped matrix
products over the experts held here; forward, rematerialised forward and backward."""

from benchmark import model_scopes

NAME = "moe_experts_ms"
UNIT = "ms"


def read(ctx):
    return model_scopes.ms_under(ctx, "moe_experts")
