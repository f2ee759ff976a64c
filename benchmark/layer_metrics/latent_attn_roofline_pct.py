"""Least time the chip could take for the causal cores of latent attention of one step over the traced
time under ``dtpu.latent_attn``. The least time is that of the core's own two products over the causal
half, whatever implements them (``flops/<config>.py``'s ``mla_scores`` and ``mla_values`` entries: a
head's query against its key, the weights against its values, ``L·(L+1)/2`` key positions a head; q,
every head's key part, the one shared rotary key and the values read and the output written once a
pass, the scores and weights ``internal``), three passes as ``roofline.py`` counts a train step; the
traced time holds the program's larger work (whole diagonal blocks, the shared key copied to every
head, the scores through HBM) and its rematerialised passes too. No implementation can beat the
products' own operations and bytes, so no reading can pass 100."""

from benchmark import model_scopes

NAME = "latent_attn_roofline_pct"
UNIT = "%"


def read(ctx):
    peaks = ctx.get("peaks")
    took = model_scopes.ms_under(ctx, "latent_attn")
    cores = [layer for layer in ctx["layers"] if layer["name"].endswith((".mla_scores", ".mla_values"))]
    if peaks is None or not took or not cores:
        return None
    least = ctx["roofline"].mxu_min_seconds_per_step(cores, ctx["batch_per_chip"], peaks)
    return 100.0 * least * 1000.0 / took
