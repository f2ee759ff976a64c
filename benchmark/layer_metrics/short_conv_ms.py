"""Device time a step spends in the ops of the program's ``dtpu.short_conv`` scope: a mixer's short causal
convolution over time and its `silu` (on the chip the kernel pair ``dtpu_short_conv_fwd`` /
``dtpu_short_conv_bwd``; elsewhere XLA's fusions), forward, rematerialised forward and backward. Finds nothing to
read where the program has no such scope."""

from benchmark import model_scopes

NAME = "short_conv_ms"
UNIT = "ms"


def read(ctx):
    return model_scopes.ms_under(ctx, "short_conv")
