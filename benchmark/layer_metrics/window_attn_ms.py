"""Device time a step spends in the ops of the program's ``dtpu.window_attn`` scope: the sliding-window causal
cores (on the chip the kernel pair ``dtpu_window<window>_attn_fwd`` / ``_bwd`` and the layout copies around
them; elsewhere XLA's band), forward, rematerialised forward and backward; inside ``dtpu.causal_attn``, so
``causal_attn_ms`` holds it too. Finds nothing to read where the program has no such scope."""

from benchmark import model_scopes

NAME = "window_attn_ms"
UNIT = "ms"


def read(ctx):
    return model_scopes.ms_under(ctx, "window_attn")
