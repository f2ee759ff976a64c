"""Matrix work of one chip's share of Nemotron-3-Super (nemotron_h), one forward pass, per row
(a sequence of ``LM.SEQ_LEN`` tokens).

Every product of the layer equations at the held sizes: a Mamba-2 layer's ``in_proj`` and
``out_proj`` and the scan's own work (`scan`: per token and head the state update and the read-out,
``2·P·N`` multiply-accumulates, whatever implements them; it reads x, B, C, Δ and writes y once);
attention's four projections and its two products over the causal half (``L·(L+1)/2`` key
positions a head; the ``L x L`` scores and weights are ``internal``, as in ``flops/vit_b16.py``);
an expert layer's router, latent projections, shared expert and the held experts' two products
(`routed`) at the *expected* share of slots, ``TOP_K · EXPERTS_HELD / EXPERTS`` a token (the router
is near uniform at initialisation; ``layer_metrics/moe_experts_roofline_pct.py`` prices the slots
the program counted), their hidden rows ``internal``; the head. The embedding is a gather and the
depthwise convolution elementwise: no matrix work.
"""

from __future__ import annotations


def _dense(name, rows, cin, cout, **extra):
    return {"name": name, "macs": rows * cin * cout, "in": rows * cin, "out": rows * cout,
            "w": cin * cout, "dgrad": True, **extra}


def layers(settings: dict) -> list[dict]:
    s = settings["LM"]
    length, dim = int(s["SEQ_LEN"]), int(s["DIM"])
    heads, p, groups, n = (int(s[k]) for k in ("MAMBA_HEADS", "MAMBA_HEAD_DIM", "MAMBA_GROUPS", "SSM_STATE"))
    inner, bc = heads * p, groups * n
    hq, hkv, hd = (int(s[k]) for k in ("ATTN_HEADS", "KV_HEADS", "HEAD_DIM"))
    experts, held, top_k = (int(s[k]) for k in ("EXPERTS", "EXPERTS_HELD", "TOP_K"))
    latent, width, shared = (int(s[k]) for k in ("LATENT", "EXPERT_WIDTH", "SHARED_WIDTH"))
    slots = length * top_k * held / experts  # expected token-expert slots on the held experts, a row
    causal = length * (length + 1) // 2
    out = []
    for i, kind in enumerate(s["PATTERN"]):
        at = f"L{i}"
        if kind == "M":
            out.append(_dense(f"{at}.in_proj", length, dim, 2 * inner + 2 * bc + heads))
            out.append({"name": f"{at}.scan", "macs": length * heads * 2 * p * n,
                        "in": length * (inner + 2 * bc + heads), "out": length * inner, "w": 0, "dgrad": True})
            out.append(_dense(f"{at}.out_proj", length, inner, dim))
        elif kind == "*":
            out.append(_dense(f"{at}.qkv", length, dim, (hq + 2 * hkv) * hd))
            out.append({"name": f"{at}.scores", "macs": hq * hd * causal, "in": length * (hq + hkv) * hd,
                        "out": hq * causal, "w": 0, "dgrad": True, "internal": hq * causal})
            out.append({"name": f"{at}.values", "macs": hq * hd * causal, "in": hq * causal + length * hkv * hd,
                        "out": length * hq * hd, "w": 0, "dgrad": True, "internal": hq * causal})
            out.append(_dense(f"{at}.o", length, hq * hd, dim))
        else:
            out.append(_dense(f"{at}.router", length, dim, experts))
            out.append(_dense(f"{at}.down", length, dim, latent))
            out.append({"name": f"{at}.routed1", "macs": slots * latent * width, "in": slots * latent,
                        "out": slots * width, "w": held * latent * width, "dgrad": True,
                        "internal": slots * width, "slots": slots})
            out.append({"name": f"{at}.routed2", "macs": slots * width * latent, "in": slots * width,
                        "out": slots * latent, "w": held * width * latent, "dgrad": True,
                        "internal": slots * width, "slots": slots})
            out.append(_dense(f"{at}.up", length, latent, dim))
            out.append(_dense(f"{at}.shared1", length, dim, shared))
            out.append(_dense(f"{at}.shared2", length, shared, dim))
    out.append(_dense("head", length, dim, int(s["VOCAB"])))
    return out
