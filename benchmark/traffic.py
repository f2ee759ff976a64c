"""The one traffic generator: a pool of seeded host batches behind the loader's contract.

A traffic mix is a file of parameters, ``benchmark/mixes/<traffic>.json``, found
by the ``traffic`` name of the cell: ``pool_batches`` and ``input_mode`` (the
per-chip batch comes from the configuration). The pool is made once from
``--seed`` in set-up by the configuration's input kind, ``benchmark/inputs/<kind>.py``
(``make_pool``): a list of host batches, each a dict of arrays with the rows
leading, every row different. ``fresh`` cycles the pool so that each step hands
``prefetch_to_device`` a different host batch with no replay marker, and every
step ships its bytes over the link as a real epoch does. ``replay`` hands it one
batch of the pool again and again under the loader's replay marker, so that it
ships once and the input layer is bypassed.
"""

from __future__ import annotations

import time

from benchmark import files

INPUT_MODES = ("fresh", "replay")
UNBOUNDED = 10**9  # an epoch "length" no window reaches


def make_pool(kind: str, seed: int, pool_batches: int, global_batch: int, settings: dict) -> list[dict]:
    """The pool of the configuration's input kind, checked: every leaf of every batch leads with the rows."""
    pool = files.load_module("inputs", kind).make_pool(seed, pool_batches, global_batch, settings)
    for batch in pool:
        for name, leaf in batch.items():
            if leaf.shape[:1] != (global_batch,):
                raise ValueError(f"input kind {kind!r}: leaf {name!r} has shape {leaf.shape}, not {global_batch} rows leading")
    return pool


class PoolLoader:
    """``set_epoch`` / ``__len__`` / ``__iter__`` over the pool.

    With ``steps`` it yields exactly that many batches. With ``seconds`` it
    yields until that much time has passed since ``start()``; the batch it is
    asked for after the deadline is the last one, and ``__len__`` says so from
    then on, which is before the loop can see that batch. So the loop takes
    its last-step fetch on it and its epoch-end bookkeeping runs inside the
    window. ``first`` is the pool index of the first batch.
    """

    def __init__(self, pool, *, steps: int | None = None, seconds: float | None = None,
                 first: int = 0, input_mode: str = "fresh", annotate=None):
        if input_mode not in INPUT_MODES:
            raise ValueError(f"input_mode {input_mode!r} not in {INPUT_MODES}")
        if (steps is None) == (seconds is None):
            raise ValueError("give steps or seconds")
        self.pool, self.steps, self.seconds, self.first = pool, steps, seconds, first
        self.replayed = None
        if input_mode == "replay":
            from distribuuuu_tpu.data.loader import REPLAY_CONST  # the loader's own promise of a batch replayed verbatim

            self.replayed = dict(pool[first % len(pool)], **{REPLAY_CONST: True})
        self.annotate = annotate
        self.yielded = 0
        self.asked_at: list[float] = []  # when the program's prefetch thread asked for each batch
        self._len = steps if steps is not None else UNBOUNDED
        self._deadline = None

    def start(self) -> float:
        """Start the window's clock; returns its ``time.monotonic()``."""
        t = time.monotonic()
        if self.seconds is not None:
            self._deadline = t + self.seconds
        return t

    def set_epoch(self, epoch: int, start_batch: int = 0) -> None:
        if start_batch:
            raise ValueError("the benchmark's loader does not resume mid-epoch")

    def __len__(self) -> int:
        return self._len

    def _next(self):
        self.asked_at.append(time.monotonic())
        batch = self.replayed or self.pool[(self.first + self.yielded) % len(self.pool)]
        self.yielded += 1
        return batch

    def __iter__(self):
        if self.seconds is not None and self._deadline is None:
            self.start()
        while self.yielded < self._len:
            if self._deadline is not None and time.monotonic() >= self._deadline:
                self._len = self.yielded + 1
            if self.annotate is None:
                yield self._next()
            else:
                with self.annotate("bench.loader.next"):
                    batch = self._next()
                yield batch
