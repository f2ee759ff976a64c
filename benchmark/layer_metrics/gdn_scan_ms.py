"""Device time a step spends in the ops of the program's ``dtpu.gdn_scan`` scope: the gated delta rule
proper (decays, the within-chunk products, the unit lower-triangular inverse, the recurrence over chunk
states, the read-out), forward, rematerialised forward and backward; not the mixer's projections, its
convolution or its gated norm. Finds nothing to read where the program has no such scope."""

from benchmark import model_scopes

NAME = "gdn_scan_ms"
UNIT = "ms"


def read(ctx):
    return model_scopes.ms_under(ctx, "gdn_scan")
