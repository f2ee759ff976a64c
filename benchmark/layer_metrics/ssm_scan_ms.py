"""Device time a step spends in the ops of the program's ``dtpu.ssm_scan`` scope: the state-space scan
proper (decays, within-chunk products, the recurrence over chunk states, the read-out), forward,
rematerialised forward and backward; not the mixer's projections or its convolution."""

from benchmark import model_scopes

NAME = "ssm_scan_ms"
UNIT = "ms"


def read(ctx):
    return model_scopes.ms_under(ctx, "ssm_scan")
