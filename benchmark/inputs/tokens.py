"""Input kind ``tokens``: rows of ``LM.SEQ_LEN + 1`` int32 token ids, uniform over the held vocabulary
slice ``0 … LM.VOCAB - 1``, every row one document at full length. Inputs and labels are one leaf
shifted (ids ``0 … L-1`` in, ``1 … L`` out), so a batch is the single leaf ``tokens``, rows leading."""

from __future__ import annotations

import numpy as np


def make_pool(seed: int, pool_batches: int, global_batch: int, settings: dict) -> list[dict[str, np.ndarray]]:
    length, vocab = int(settings["LM"]["SEQ_LEN"]), int(settings["LM"]["VOCAB"])
    rng = np.random.default_rng(seed)
    return [{"tokens": rng.integers(0, vocab, (global_batch, length + 1), dtype=np.int32)}
            for _ in range(pool_batches)]
