"""Least time the chip could take for the gated delta rules of one step over the traced time under
``dtpu.gdn_scan``. The least time is that of the recurrence itself, whatever implements it
(``flops/<config>.py``'s ``gdn`` entries: per token and value head the read of the decayed state, the
write and the read-out; q, k, v, decay and write strength read and o written once a pass), three
passes as ``roofline.py`` counts a train step; the traced time holds the chunked algorithm's larger
work (the triangular inverse, the products with the chunk's state) and the rematerialised forward pass
too. No implementation can beat the recurrence's own operations and bytes, so no reading can pass 100."""

from benchmark import model_scopes

NAME = "gdn_scan_roofline_pct"
UNIT = "%"


def read(ctx):
    peaks = ctx.get("peaks")
    took = model_scopes.ms_under(ctx, "gdn_scan")
    rules = [layer for layer in ctx["layers"] if layer["name"].endswith(".gdn")]
    if peaks is None or not took or not rules:
        return None
    least = ctx["roofline"].mxu_min_seconds_per_step(rules, ctx["batch_per_chip"], peaks)
    return 100.0 * least * 1000.0 / took
