"""Slowest journal ``window`` record of the measured window, per step (host clock, program's journal)."""

NAME = "loop_window_ms_max"
UNIT = "ms/step"


def read(ctx):
    epoch = ctx["window"]["epoch"]
    times = [r["step_time"] for r in ctx["journal"]
             if r["kind"] == "window" and r["epoch"] == epoch and not r["warmup"]]
    return 1000.0 * max(times) if times else None
