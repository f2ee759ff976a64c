"""Input kind ``image``: u8 images of ``TRAIN.IM_SIZE`` and one label a row, uniform over
``MODEL.NUM_CLASSES``, every row different. The draws and their order are those of PR 25's
``traffic.make_pool``, so a seed's pool is the same bytes (``tests/test_inputs.py`` holds the digests)."""

from __future__ import annotations

import numpy as np


def make_pool(seed: int, pool_batches: int, global_batch: int, settings: dict) -> list[dict[str, np.ndarray]]:
    im_size = int(settings["TRAIN"]["IM_SIZE"])
    num_classes = int(settings["MODEL"]["NUM_CLASSES"])
    rng = np.random.default_rng(seed)
    pool = []
    for _ in range(pool_batches):
        pool.append({
            "image": rng.integers(0, 256, (global_batch, im_size, im_size, 3), dtype=np.uint8),
            "label": rng.integers(0, num_classes, global_batch).astype(np.int32),
            "weight": np.ones((global_batch,), np.float32),
        })
    return pool
