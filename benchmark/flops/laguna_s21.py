"""Matrix work of one chip's share of Laguna-S-2.1 (laguna), one forward pass, per row (a sequence of
``LM.SEQ_LEN`` tokens).

Every product of the layer equations at the held sizes: an attention layer's ``q`` (its kind's query
heads), ``kv`` (the key heads' keys and values), ``gate`` (a head's own) and ``o``, and its core's own
two products: on a full layer (``D``, ``F``) over the causal half, ``L·(L+1)/2`` key positions a head
(`scores`, `values`, as ``flops/qwen3_next.py`` counts them); on a window layer (``W``) over the band,
``Σ_i min(i + 1, window)`` key positions a head (`swa_scores`, `swa_values`: the names are their own,
so that ``layer_metrics/window_attn_roofline_pct.py`` finds them and no other family's reader does);
the ``L x L`` scores and weights ``internal``. A dense layer's gated feed-forward (``ff1``: gate and up
side by side); an expert layer's router, shared expert and its gate and the held experts' two products
(`routed`, the first of twice the expert's width) at the *expected* share of slots, ``TOP_K ·
EXPERTS_HELD / EXPERTS`` a token (the router is near uniform at initialisation;
``layer_metrics/moe_experts_roofline_pct.py`` prices the slots the program counted), their hidden rows
``internal``; the head. The embedding is a gather, the norms, the rotary embedding and the gates'
sigmoids elementwise: no matrix work.
"""

from __future__ import annotations


def _dense(name, rows, cin, cout, **extra):
    return {"name": name, "macs": rows * cin * cout, "in": rows * cin, "out": rows * cout,
            "w": cin * cout, "dgrad": True, **extra}


def band_positions(length: int, window: int) -> int:
    """Key positions a head of a window layer meets over a row: ``Σ_i min(i + 1, window)``."""
    seen = min(length, window)
    return seen * (seen + 1) // 2 + (length - seen) * window


def layers(settings: dict) -> list[dict]:
    s = settings["LM"]
    length, dim, hd, hkv = (int(s[k]) for k in ("SEQ_LEN", "DIM", "HEAD_DIM", "KV_HEADS"))
    experts, held, top_k = (int(s[k]) for k in ("EXPERTS", "EXPERTS_HELD", "TOP_K"))
    width, shared, dense = int(s["EXPERT_WIDTH"]), int(s["SHARED_WIDTH"]), int(s["DENSE_WIDTH"])
    slots = length * top_k * held / experts  # expected token-expert slots on the held experts, a row
    out = []
    for i, kind in enumerate(s["PATTERN"]):
        at = f"L{i}"
        h = int(s["WINDOW_HEADS"] if kind == "W" else s["FULL_HEADS"])
        core, seen = ("swa_", band_positions(length, int(s["WINDOW"]))) if kind == "W" else ("", length * (length + 1) // 2)
        out.append(_dense(f"{at}.q", length, dim, h * hd))
        out.append(_dense(f"{at}.kv", length, dim, 2 * hkv * hd))
        out.append({"name": f"{at}.{core}scores", "macs": h * hd * seen, "in": length * (h + hkv) * hd,
                    "out": h * seen, "w": 0, "dgrad": True, "internal": h * seen})
        out.append({"name": f"{at}.{core}values", "macs": h * hd * seen, "in": h * seen + length * hkv * hd,
                    "out": length * h * hd, "w": 0, "dgrad": True, "internal": h * seen})
        out.append(_dense(f"{at}.gate", length, dim, h))
        out.append(_dense(f"{at}.o", length, h * hd, dim))
        if kind == "D":
            out.append(_dense(f"{at}.ff1", length, dim, 2 * dense))
            out.append(_dense(f"{at}.ff2", length, dense, dim))
            continue
        out.append(_dense(f"{at}.router", length, dim, experts))
        out.append({"name": f"{at}.routed1", "macs": slots * dim * 2 * width, "in": slots * dim,
                    "out": slots * 2 * width, "w": held * dim * 2 * width, "dgrad": True,
                    "internal": slots * 2 * width, "slots": slots})
        out.append({"name": f"{at}.routed2", "macs": slots * width * dim, "in": slots * width,
                    "out": slots * dim, "w": held * width * dim, "dgrad": True,
                    "internal": slots * width, "slots": slots})
        out.append(_dense(f"{at}.shared1", length, dim, 2 * shared))
        out.append(_dense(f"{at}.shared2", length, shared, dim))
        out.append(_dense(f"{at}.shared_gate", length, dim, 1))
    out.append(_dense("head", length, dim, int(s["VOCAB"])))
    return out
