"""Sum of backend-compile durations during set-up, from the program's monitoring bridge:
the run's total less the measured window's (which should be nothing)."""

NAME = "compile_s"
UNIT = "s"

EVENT = "/jax/core/compile/backend_compile_duration"


def read(ctx):
    epoch = ctx["window"]["epoch"]
    total = in_window = None
    for r in ctx["journal"]:
        if r["kind"] != "counters":
            continue
        seconds = r["durations"].get(EVENT, {}).get("total_s", 0.0)
        if r.get("scope") == "run":
            total = seconds
        elif r.get("scope") == "epoch" and r.get("epoch") == epoch:
            in_window = seconds
    if total is None:
        return None
    return total - (in_window or 0.0)
