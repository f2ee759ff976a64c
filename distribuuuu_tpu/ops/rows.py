"""The rows of a batch in groups, for a path whose temporaries grow with the rows and need not all stand at once."""

from __future__ import annotations

import jax
from jax import lax

#: What the temporaries of one group of rows may take: 1/32 of a v5e's 16 GiB of HBM. A token model's state
#: (weights, gradients, and in the benchmark a second copy of the weights) is about half the chip and a layer's kept
#: activations much of the rest, and a path's temporaries stand twice over while its backward pass runs: a few of
#: them at 1/32 each is what is left. A batch whose temporaries stay inside it goes through in one piece.
GROUP_BYTES = 2**34 // 32


def rows_in_groups(fn, args, bytes_per_row: int, remat: bool = False):
    """``fn(*args)`` over arrays that lead with the batch's ``B`` rows, the rows taken in the largest groups whose
    temporaries (``bytes_per_row`` a row, the caller's count) stay inside `GROUP_BYTES`, one group after the other
    (`lax.map`): the same products, fewer of them live at a time. Groups of one row where no larger group divides
    ``B``; with ``remat`` a group keeps its inputs alone for the backward pass. ``fn`` itself where all rows fit."""
    batch = args[0].shape[0]
    rows = max(1, GROUP_BYTES // bytes_per_row)
    if rows >= batch:
        return fn(*args)
    if batch % rows:
        rows = 1
    one_group = lambda group: fn(*group)
    grouped = tuple(t.reshape(batch // rows, rows, *t.shape[1:]) for t in args)
    out = lax.map(jax.checkpoint(one_group) if remat else one_group, grouped)
    return out.reshape(batch, *out.shape[2:])
