"""Device time a step spends in the ops of the program's ``dtpu.dense_ffn`` scope: the dense feed-forwards
beside or instead of the experts (shared experts, with a shared gate where the family has one; a leading
dense layer), forward, rematerialised forward and backward. Finds nothing to read where the program has no
such scope."""

from benchmark import model_scopes

NAME = "dense_ffn_ms"
UNIT = "ms"


def read(ctx):
    return model_scopes.ms_under(ctx, "dense_ffn")
