"""Busiest held expert's tokens over the mean held expert's, worst expert layer, worst step of the
measured window: the program's ``moe_load_max_over_mean`` counter of its journal's ``window`` records.
1 is an even load; what passes the room of a round costs the layer a second round of its products."""

from benchmark import model_scopes

NAME = "moe_load_max_over_mean"
UNIT = "ratio"


def read(ctx):
    values = model_scopes.window_counter(ctx, NAME)
    return max(values) if values else None
