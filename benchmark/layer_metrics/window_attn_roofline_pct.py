"""Least time the chip could take for the sliding-window causal cores of one step over the traced time
under ``dtpu.window_attn``. The least time is that of the core's own two products over the band, whatever
implements them (``flops/<config>.py``'s ``swa_scores`` and ``swa_values`` entries: a head's query
against its keys, the weights against its values, ``Σ_i min(i + 1, window)`` key positions a head; q, the
key heads' keys and values read and the output written once a pass, the scores and weights ``internal``),
three passes as ``roofline.py`` counts a train step; the traced time holds the program's larger work (the
masked parts of the band's edge tiles, the layout copies) and its rematerialised passes too. No
implementation can beat the products' own operations and bytes, so no reading can pass 100."""

from benchmark import model_scopes

NAME = "window_attn_roofline_pct"
UNIT = "%"


def read(ctx):
    peaks = ctx.get("peaks")
    took = model_scopes.ms_under(ctx, "window_attn")
    cores = [layer for layer in ctx["layers"] if layer["name"].endswith((".swa_scores", ".swa_values"))]
    if peaks is None or not took or not cores:
        return None
    least = ctx["roofline"].mxu_min_seconds_per_step(cores, ctx["batch_per_chip"], peaks)
    return 100.0 * least * 1000.0 / took
