"""Whole-step model FLOP/s utilisation: model FLOPs per image (3 x forward, 2 per MAC, no
recomputation) x images per second per chip of the traced window, over the chip's bf16 peak."""

NAME = "step_mfu_pct"
UNIT = "%"


def read(ctx):
    trace, peaks = ctx["trace"], ctx.get("peaks")
    if trace is None or peaks is None:
        return None
    period = trace.step_period_ms()
    if period is None:
        return None
    img_per_s = ctx["batch_per_chip"] / (period / 1000.0)
    return 100.0 * ctx["roofline"].train_flops_per_image(ctx["layers"]) * img_per_s / peaks["bf16_flops_per_s"]
