"""Device time a step spends in the ops of the program's ``dtpu.lm_head`` scope: the head's product over the
held vocabulary in each of the loss's blocks of tokens, forward, recomputed and backward (inside
``dtpu.loss``, whose softmax and sums it leaves out). Finds nothing to read where the program has no such
scope."""

from benchmark import model_scopes

NAME = "lm_head_ms"
UNIT = "ms"


def read(ctx):
    return model_scopes.ms_under(ctx, "lm_head")
