"""Matrix work of one chip's share of Qwen3-Next-80B-A3B (qwen3_next), one forward pass, per row
(a sequence of ``LM.SEQ_LEN`` tokens).

Every product of the layer equations at the held sizes: a delta-net layer's ``in_qkvz``, ``in_ba``
and ``out`` and the recurrence's own work (`gdn`: per token and value head the read of the decayed
state, the write and the read-out, ``3·K·V`` multiply-accumulates, whatever implements them; it
reads q, k, v, the decay and the write strength and writes o once; the name does not end in
``.scan``, which is the state-space scan's and another reader's); attention's projections (a
head's query and its gate from ``q``) and its two products over the causal half (``L·(L+1)/2`` key
positions a head; the ``L x L`` scores and weights are ``internal``, as in ``flops/vit_b16.py``);
every layer's expert block: the router, the shared expert with its gate, and the held experts' two
products (`routed`, the first of twice the expert's width: gate and up side by side) at the
*expected* share of slots, ``TOP_K · EXPERTS_HELD / EXPERTS`` a token (the router is near uniform at
initialisation; ``layer_metrics/moe_experts_roofline_pct.py`` prices the slots the program
counted), their hidden rows ``internal``; the head. The embedding is a gather, the depthwise
convolution, the norms, the rotary embedding and the gates elementwise: no matrix work.
"""

from __future__ import annotations


def _dense(name, rows, cin, cout, **extra):
    return {"name": name, "macs": rows * cin * cout, "in": rows * cin, "out": rows * cout,
            "w": cin * cout, "dgrad": True, **extra}


def layers(settings: dict) -> list[dict]:
    s = settings["LM"]
    length, dim = int(s["SEQ_LEN"]), int(s["DIM"])
    hk, hv, dk, dv = (int(s[k]) for k in ("LINEAR_KEY_HEADS", "LINEAR_VALUE_HEADS", "LINEAR_KEY_DIM", "LINEAR_VALUE_DIM"))
    keys, values = hk * dk, hv * dv
    hq, hkv, hd = (int(s[k]) for k in ("ATTN_HEADS", "KV_HEADS", "HEAD_DIM"))
    experts, held, top_k = (int(s[k]) for k in ("EXPERTS", "EXPERTS_HELD", "TOP_K"))
    width, shared = int(s["EXPERT_WIDTH"]), int(s["SHARED_WIDTH"])
    slots = length * top_k * held / experts  # expected token-expert slots on the held experts, a row
    causal = length * (length + 1) // 2
    out = []
    for i, kind in enumerate(s["PATTERN"]):
        at = f"L{i}"
        if kind == "G":
            out.append(_dense(f"{at}.in_qkvz", length, dim, 2 * keys + 2 * values))
            out.append(_dense(f"{at}.in_ba", length, dim, 2 * hv))
            out.append({"name": f"{at}.gdn", "macs": length * hv * 3 * dk * dv,
                        "in": length * (2 * keys + values + 2 * hv), "out": length * values, "w": 0, "dgrad": True})
            out.append(_dense(f"{at}.out", length, values, dim))
        else:
            out.append(_dense(f"{at}.q", length, dim, 2 * hq * hd))
            out.append(_dense(f"{at}.kv", length, dim, 2 * hkv * hd))
            out.append({"name": f"{at}.scores", "macs": hq * hd * causal, "in": length * (hq + hkv) * hd,
                        "out": hq * causal, "w": 0, "dgrad": True, "internal": hq * causal})
            out.append({"name": f"{at}.values", "macs": hq * hd * causal, "in": hq * causal + length * hkv * hd,
                        "out": length * hq * hd, "w": 0, "dgrad": True, "internal": hq * causal})
            out.append(_dense(f"{at}.o", length, hq * hd, dim))
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
