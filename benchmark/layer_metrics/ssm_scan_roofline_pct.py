"""Least time the chip could take for the state-space scans of one step over the traced time under
``dtpu.ssm_scan``. The least time is that of the recurrence itself, whatever implements it
(``flops/<config>.py``'s ``scan`` entries: per token and head the state update and the read-out;
x, B, C, Δ read and y written once a pass), three passes as ``roofline.py`` counts a train step; the
traced time holds the chunked algorithm's larger work and the rematerialised forward pass too. No
implementation can beat the recurrence's own operations and bytes, so no reading can pass 100."""

from benchmark import model_scopes

NAME = "ssm_scan_roofline_pct"
UNIT = "%"


def read(ctx):
    peaks = ctx.get("peaks")
    took = model_scopes.ms_under(ctx, "ssm_scan")
    scans = [layer for layer in ctx["layers"] if layer["name"].endswith(".scan")]
    if peaks is None or not took or not scans:
        return None
    least = ctx["roofline"].mxu_min_seconds_per_step(scans, ctx["batch_per_chip"], peaks)
    return 100.0 * least * 1000.0 / took
