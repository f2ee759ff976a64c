"""Share of ``step_device_ms`` in ops that no scope of the program places: ops of every computation (loop
bodies included) whose ``op_name``, as ``model_scopes.op_name_of`` places a fusion by the product it holds,
has no ``dtpu.<scope>`` component, and ops the map does not hold at all. Loops, conditionals and calls are
left out: they only span ops that are counted themselves. The remainder the scope metrics leave: norms,
rotary embeddings, convolutions, the embedding and its gradient, copies XLA makes with no ``op_name``, and
the loss's own ops outside the head (its ``dtpu.loss`` stands inside ``jvp(...)``, as no reader's scope does)."""

from benchmark import hlo, model_scopes, scopes

NAME = "step_unplaced_pct"
UNIT = "%"


def unplaced(text: str):
    """``keep`` for `scopes.ms_per_step`: an instruction that runs on its own and lies under no scope."""
    placed = model_scopes.op_name_of(text)
    spanning = {name for name, (opcode, _, _) in hlo.parse(text).items() if opcode in model_scopes.SPANNING}
    return lambda op: op not in spanning and not any(
        part.startswith(scopes.SCOPE_PREFIX) for part in placed.get(op, "").split("/"))


def read(ctx):
    trace = ctx["trace"]
    text = scopes.step_hlo_text(ctx) if trace is not None else None
    whole = trace.step_device_ms() if text is not None else None
    if not whole:
        return None
    return 100.0 * (scopes.ms_per_step(trace, unplaced(text)) or 0.0) / whole
