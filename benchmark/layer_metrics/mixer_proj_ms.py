"""Device time a step spends in the ops of the program's ``dtpu.mixer_proj`` scope: the products that carry
the stream into and out of a mixer (attention's q, k, v and o, the state-space and delta-rule mixers' input
and output projections, latent attention's ``kv_a`` / ``kv_b``, an expert layer's latent ``down`` / ``up``),
forward, rematerialised forward and backward; not the norms, convolutions, rotary embeddings or gates. Finds
nothing to read where the program has no such scope."""

from benchmark import model_scopes

NAME = "mixer_proj_ms"
UNIT = "ms"


def read(ctx):
    return model_scopes.ms_under(ctx, "mixer_proj")
