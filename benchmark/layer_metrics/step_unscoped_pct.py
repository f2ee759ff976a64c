"""Share of ``step_device_ms`` in ops that no phase owns (no ``jvp(``, ``transpose(`` or ``dtpu.``
scope in their metadata): the measure of the attribution itself."""

from benchmark import scopes

NAME = "step_unscoped_pct"
UNIT = "%"


def read(ctx):
    trace = ctx["trace"]
    text = scopes.step_hlo_text(ctx) if trace is not None else None
    whole = trace.step_device_ms() if text is not None else None
    if not whole:
        return None
    return 100.0 * (scopes.phase_ms_per_step(trace, scopes.scope_of(text), (scopes.UNSCOPED,)) or 0.0) / whole
