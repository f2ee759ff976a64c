"""The program's ``data_wait_s`` counter (step loop blocked on the prefetch queue) over the window's seconds."""

NAME = "data_wait_pct"
UNIT = "%"


def read(ctx):
    epoch = ctx["window"]["epoch"]
    for r in ctx["journal"]:
        if r["kind"] == "counters" and r.get("scope") == "epoch" and r.get("epoch") == epoch:
            return 100.0 * r["waits"].get("data_wait_s", 0.0) / ctx["window"]["seconds"]
    return None
