"""Matrix work of ViT-B/16, one forward pass, per image.

Patch embedding, 12 blocks of (qkv, scores, weighted values, projection,
MLP in, MLP out), and the head on the class token. The two attention products
have no weights: both operands are activations (``w`` is 0 and ``in`` holds
both). The ``L x L`` scores (the first product's result) and weights (the
second's operand) are ``internal``: a fused implementation never writes them,
so they are no part of the work's least bytes (``roofline.py``).
"""

from __future__ import annotations

PATCH, DIM, DEPTH, HEADS, MLP = 16, 768, 12, 12, 3072


def _dense(name, rows, cin, cout, dgrad=True):
    return {"name": name, "macs": rows * cin * cout, "in": rows * cin, "out": rows * cout,
            "w": cin * cout, "dgrad": dgrad}


def layers(settings: dict) -> list[dict]:
    size = int(settings["TRAIN"]["IM_SIZE"])
    classes = int(settings["MODEL"]["NUM_CLASSES"])
    patches = (size // PATCH) ** 2
    tokens = patches + 1
    hd = DIM // HEADS
    out = [_dense("patch", patches, PATCH * PATCH * 3, DIM, dgrad=False)]
    for i in range(DEPTH):
        p = f"blk{i}"
        out.append(_dense(f"{p}.qkv", tokens, DIM, 3 * DIM))
        out.append({"name": f"{p}.scores", "macs": HEADS * tokens * tokens * hd,
                    "in": 2 * tokens * DIM, "out": HEADS * tokens * tokens, "w": 0,
                    "internal": HEADS * tokens * tokens})
        out.append({"name": f"{p}.values", "macs": HEADS * tokens * tokens * hd,
                    "in": HEADS * tokens * tokens + tokens * DIM, "out": tokens * DIM, "w": 0,
                    "internal": HEADS * tokens * tokens})
        out.append(_dense(f"{p}.proj", tokens, DIM, DIM))
        out.append(_dense(f"{p}.fc1", tokens, DIM, MLP))
        out.append(_dense(f"{p}.fc2", tokens, MLP, DIM))
    out.append(_dense("head", 1, DIM, classes))
    return out
