"""Device time a step spends in the ops of the program's ``dtpu.latent_attn`` scope: the causal core of
latent attention (the keys formed whole a head, scores, mask, softmax, weighted values), forward,
rematerialised forward and backward; not the mixer's projections, the latent's norm or the rotary
embedding. Finds nothing to read where the program has no such scope."""

from benchmark import model_scopes

NAME = "latent_attn_ms"
UNIT = "ms"


def read(ctx):
    return model_scopes.ms_under(ctx, "latent_attn")
