"""Device time a step spends in the ops of the program's ``dtpu.causal_attn`` scope: every family's causal
core (on the chip the kernel pair ``dtpu_causal_attn_fwd`` / ``dtpu_causal_attn_bwd`` and the layout copies
around them; elsewhere XLA's blocks), forward, rematerialised forward and backward; inside ``dtpu.latent_attn``
where the core is latent attention's. Finds nothing to read where the program has no such scope."""

from benchmark import model_scopes

NAME = "causal_attn_ms"
UNIT = "ms"


def read(ctx):
    return model_scopes.ms_under(ctx, "causal_attn")
