"""Peak bytes in use on the fullest device after the window (the allocator's own counter)."""

NAME = "hbm_peak_gib"
UNIT = "GiB"


def read(ctx):
    peak = ctx["device"].get("memory_peak_bytes", 0)
    return peak / 2**30 if peak else None
