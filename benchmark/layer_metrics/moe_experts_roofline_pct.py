"""Least time the chip could take for the held experts' grouped products of one step over the traced
time under ``dtpu.moe_experts``. The work is that of the token-expert slots the program counted
(``moe_slots_here`` of the measured window's journal records, a step): ``flops/<config>.py``'s
``routed`` entries, priced at the expected share, scaled to the counted one; per pass the larger of
FLOPs over peak and bytes over peak bandwidth, the held weights read and the routed rows read and
written once, the hidden rows ``internal``; three passes. Rows the implementation pads its rounds
with, and its rematerialised pass, are in the traced time and not in the work: under 100 always."""

import statistics

from benchmark import model_scopes

NAME = "moe_experts_roofline_pct"
UNIT = "%"


def read(ctx):
    peaks = ctx.get("peaks")
    took = model_scopes.ms_under(ctx, "moe_experts")
    counted = model_scopes.window_counter(ctx, "moe_slots_here")
    routed = [layer for layer in ctx["layers"] if "slots" in layer]
    if peaks is None or not took or not counted or not routed:
        return None
    rows = ctx["batch_per_chip"]
    expected = rows * sum(layer["slots"] for layer in routed) / 2  # two entries a layer share its slots
    share = statistics.mean(counted) / ctx["chips"] / expected
    scaled = [dict(layer, **{k: layer[k] * share for k in ("macs", "in", "out", "internal")}) for layer in routed]
    least = ctx["roofline"].mxu_min_seconds_per_step(scaled, rows, peaks)
    return 100.0 * least * 1000.0 / took
