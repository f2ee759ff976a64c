"""Matrix work of one chip's share of kanana-2-30b-a3b-instruct-2601 (deepseek_v3), one forward
pass, per row (a sequence of ``LM.SEQ_LEN`` tokens).

Every product of the layer equations at the held sizes: latent attention's projections (``q``;
``kv_a`` to the latent and the shared rotary key; ``kv_b`` from the normed latent to every head's
key part and value; ``o``) and the core's own two products in the expanded form, whatever
implements them (`mla_scores`: a head's 192-wide query against its key over the causal half,
``L·(L+1)/2`` key positions a head, reading q, every head's key part and the one shared rotary key
once; `mla_values`: the weights against a head's 128-wide values; the ``L x L`` scores and weights
are ``internal``, as in ``flops/vit_b16.py``; the names are the core's own, so that
``layer_metrics/latent_attn_roofline_pct.py`` finds them and no other family's reader does); a
dense layer's gated feed-forward (``ff1``: gate and up side by side); an expert layer's router,
shared experts and the held experts' two products (`routed`, the first of twice the expert's
width: gate and up side by side) at the *expected* share of slots, ``TOP_K · EXPERTS_HELD /
EXPERTS`` a token (the router is near uniform at initialisation;
``layer_metrics/moe_experts_roofline_pct.py`` prices the slots the program counted), their hidden
rows ``internal``; the head. The embedding is a gather, the norms, the rotary embedding and the
gates elementwise: no matrix work.
"""

from __future__ import annotations


def _dense(name, rows, cin, cout, **extra):
    return {"name": name, "macs": rows * cin * cout, "in": rows * cin, "out": rows * cout,
            "w": cin * cout, "dgrad": True, **extra}


def layers(settings: dict) -> list[dict]:
    s = settings["LM"]
    length, dim = int(s["SEQ_LEN"]), int(s["DIM"])
    heads, latent, dn, dr, dv = (int(s[k]) for k in ("ATTN_HEADS", "KV_LATENT", "QK_NOPE_DIM", "QK_ROPE_DIM", "V_HEAD_DIM"))
    experts, held, top_k = (int(s[k]) for k in ("EXPERTS", "EXPERTS_HELD", "TOP_K"))
    width, shared, dense = int(s["EXPERT_WIDTH"]), int(s["SHARED_WIDTH"]), int(s["DENSE_WIDTH"])
    slots = length * top_k * held / experts  # expected token-expert slots on the held experts, a row
    causal = length * (length + 1) // 2
    out = []
    for i, kind in enumerate(s["PATTERN"]):
        at = f"L{i}"
        out.append(_dense(f"{at}.q", length, dim, heads * (dn + dr)))
        out.append(_dense(f"{at}.kv_a", length, dim, latent + dr))
        out.append(_dense(f"{at}.kv_b", length, latent, heads * (dn + dv)))
        out.append({"name": f"{at}.mla_scores", "macs": heads * (dn + dr) * causal,
                    "in": length * (heads * (dn + dr) + heads * dn + dr), "out": heads * causal,
                    "w": 0, "dgrad": True, "internal": heads * causal})
        out.append({"name": f"{at}.mla_values", "macs": heads * dv * causal, "in": heads * causal + length * heads * dv,
                    "out": length * heads * dv, "w": 0, "dgrad": True, "internal": heads * causal})
        out.append(_dense(f"{at}.o", length, heads * dv, dim))
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
    out.append(_dense("head", length, dim, int(s["VOCAB"])))
    return out
