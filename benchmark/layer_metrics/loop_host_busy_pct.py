"""Share of the window's wall that the loop thread spent in none of its named waits: 1 - (``data_wait_s`` +
``throttle_s`` + ``fetch_wait_s``) / window seconds, from the window epoch's ``counters`` record. What is left is the
host's own work (the ``train_step`` call, bookkeeping) and time the thread did not run: how near the host is to
setting the pace."""

NAME = "loop_host_busy_pct"
UNIT = "%"
WAITS = ("data_wait_s", "throttle_s", "fetch_wait_s")


def read(ctx):
    epoch = ctx["window"]["epoch"]
    for r in ctx["journal"]:
        if r["kind"] == "counters" and r.get("scope") == "epoch" and r.get("epoch") == epoch:
            waits = r["waits"]
            if any(name not in waits for name in WAITS):  # a program without the phases: nothing to read
                return None
            return 100.0 * (1.0 - sum(waits[name] for name in WAITS) / ctx["window"]["seconds"])
    return None
