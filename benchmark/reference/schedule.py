"""Plain reference: the epoch-granular learning rate (cosine with linear warm-up)."""

from __future__ import annotations

import math


def lr_at_epoch(hp: dict, epoch: int) -> float:
    if hp["LR_POLICY"] != "cos":
        raise ValueError("reference schedule covers the shipped cosine policy only")
    lr = 0.5 * (1.0 + math.cos(math.pi * epoch / hp["MAX_EPOCH"]))
    lr = ((1.0 - hp["MIN_LR"]) * lr + hp["MIN_LR"]) * hp["BASE_LR"]
    if epoch < hp["WARMUP_EPOCHS"]:
        alpha = epoch / hp["WARMUP_EPOCHS"]
        lr *= hp["WARMUP_FACTOR"] * (1.0 - alpha) + alpha
    return lr
