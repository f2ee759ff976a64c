"""Which layers of a pattern a flat leaf of `models/token_lm.TokenLM` holds: shared by the token families' tests,
whose references keep one leaf a layer (``L3.w1``) where the program stacks the scanned unit's (``U1_w1 [repeats, ...]``)."""

from distribuuuu_tpu.models import token_lm


def layers_of(pattern: str) -> dict:
    """Prefix -> the list of layers a scanned ``U<j>`` stacks, or the one layer an ``L<i>`` is."""
    first, unit, repeats = token_lm.repeated_unit(pattern)
    scanned = unit * repeats if repeats > 1 else 0
    out = {f"U{j}": [first + r * unit + j for r in range(repeats)] for j in range(unit)} if scanned else {}
    out.update({f"L{i}": i for i in range(len(pattern)) if not first <= i < first + scanned})
    return out


def from_program(tree: dict, pattern: str) -> dict:
    """The program's flat leaves back as the reference's per-layer ones."""
    where_of = layers_of(pattern)
    out = {}
    for name, value in tree.items():
        prefix, _, short = name.partition("_")
        where = where_of.get(prefix)
        if isinstance(where, list):
            out.update({f"L{i}.{short}": value[r] for r, i in enumerate(where)})
        else:
            out[name if where is None else f"L{where}.{short}"] = value
    return out
