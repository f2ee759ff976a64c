"""Device time a step spends in the ops of the program's ``dtpu.moe_route`` scope: an expert layer's
scores, top-k and weights, sorting the tokens to the experts held, gathering their rows and adding the
weighted results back; forward, rematerialised forward and backward."""

from benchmark import model_scopes

NAME = "moe_route_ms"
UNIT = "ms"


def read(ctx):
    return model_scopes.ms_under(ctx, "moe_route")
