"""The causal attention pair's cost files price a call from its shapes over the causal half, and
`hlo.kernel_calls` finds both inside the scanned unit's ``while`` and in the leading layer: the cores of
``kanana2_30b.train`` run there. The forward's count is one pass of the configuration's own ``mla_scores``
+ ``mla_values``, so that ``kernel_roofline_pct`` can never read a whole ``L x L`` against a kernel that
skips half of it."""

import pytest

from benchmark import files, harness, hlo, roofline

# A made text: a ``while`` whose body calls the forward and the backward kernel as the compiled step of
# ``kanana2_30b.train`` calls them (operands, results and metadata as there: q of 192, a head's keys and values
# side by side, the shared rotary key, dO, the rows' statistics), and the leading layer's forward in the entry
# computation.
Q = "bf16[2,32,8192,192]"
KV = "bf16[2,32,8192,256]"
KR = "bf16[2,8192,64]"
O = "bf16[2,32,8192,128]"
LSE = "f32[2,32,1,8192]"
STATS = "f32[2,32,2,8192]"
DKR = "f32[2,32,8192,64]"


def _call(name, results, operands, where):
    names = ", ".join(f"%{n}" for n, _ in operands)
    constraints = ", ".join(t for _, t in operands)
    return (f'  %{name} = ({", ".join(results)}) custom-call({names}), custom_call_target="tpu_custom_call", '
            f'operand_layout_constraints={{{constraints}}}, frontend_attributes={{kernel_metadata={{}}}}, '
            f'metadata={{op_name="jit(step_training)/{where}/dtpu.latent_attn/{name.split(".")[0]}/pallas_call" '
            f'stack_frame_id=1}}, backend_config={{"custom_call_config":{{"body":"..."}}}}')


FWD_IN = [("q", Q), ("kv", KV), ("kr", KR)]
BWD_IN = FWD_IN + [("do", O), ("stats", STATS)]
TEXT = "\n".join([
    "HloModule jit_step_training", "",
    f"%body.1 (p: ({O}, {O})) -> ({O}, {O}) {{",
    f"  %p = ({O}{{3,2,1,0}}, {O}{{3,2,1,0}}) parameter(0)",
    *(f"  %{n} = {t}{{3,2,1,0}} constant({{0}})" for n, t in BWD_IN),
    _call("dtpu_causal_attn_fwd.14", [O, LSE], FWD_IN, "jvp(DeepseekV3)/while/body/closed_call/U0"),
    _call("dtpu_causal_attn_bwd.12", [Q, KV, DKR], BWD_IN,
          "transpose(jvp(DeepseekV3))/while/body/closed_call/U0/U0/checkpoint"),
    f"  ROOT %next.1 = ({O}{{3,2,1,0}}, {O}{{3,2,1,0}}) tuple(%do, %do)",
    "}", "",
    f"%cond.1 (p.1: ({O}, {O})) -> pred[] {{",
    f"  %p.1 = ({O}{{3,2,1,0}}, {O}{{3,2,1,0}}) parameter(0)",
    "  ROOT %go.1 = pred[] constant(false)",
    "}", "",
    f"ENTRY %main.1 (a: ({O}, {O})) -> ({O}, {O}) {{",
    f"  %a = ({O}{{3,2,1,0}}, {O}{{3,2,1,0}}) parameter(0)",
    *(f"  %{n} = {t}{{3,2,1,0}} constant({{0}})" for n, t in FWD_IN),
    _call("dtpu_causal_attn_fwd.13", [O, LSE], FWD_IN, "jvp(DeepseekV3)/L0"),
    f"  ROOT %while.1 = ({O}{{3,2,1,0}}, {O}{{3,2,1,0}}) while(%a), condition=%cond.1, body=%body.1",
    "}", ""])

B, H, L, DK, DR, DV = 2, 32, 8192, 128, 64, 128
CAUSAL = B * H * L * (L + 1) // 2
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_kernel_calls_finds_the_pair_inside_the_loop_and_in_the_leading_layer():
    calls = hlo.kernel_calls(TEXT)
    assert {name: call["kernel"] for name, call in calls.items()} == {
        "dtpu_causal_attn_fwd.13": "dtpu_causal_attn_fwd", "dtpu_causal_attn_fwd.14": "dtpu_causal_attn_fwd",
        "dtpu_causal_attn_bwd.12": "dtpu_causal_attn_bwd"}
    fwd, bwd = calls["dtpu_causal_attn_fwd.14"], calls["dtpu_causal_attn_bwd.12"]
    assert fwd["operands"] == [("bf16", (B, H, L, DK + DR)), ("bf16", (B, H, L, DK + DV)), ("bf16", (B, L, DR))]
    assert fwd["results"] == [("bf16", (B, H, L, DV)), ("f32", (B, H, 1, L))]
    assert len(bwd["operands"]) == 5 and bwd["results"][-1] == ("f32", (B, H, L, DR))
    assert all(hlo.classify(TEXT)[name] == "kernel" for name in calls)


def test_the_cost_files_price_the_causal_half_of_the_products_the_kernels_perform():
    """Forward: scores at dk + dr and values at dv, a position of the causal half; backward: the scores again,
    dP and dV at dv, dQ and dK at dk + dr. Never ``L²``: the kernels skip the tiles above the diagonal."""
    costs = roofline.kernel_costs(hlo.kernel_calls(TEXT))
    fwd, bwd = costs["dtpu_causal_attn_fwd.13"], costs["dtpu_causal_attn_bwd.12"]
    assert fwd == costs["dtpu_causal_attn_fwd.14"] and fwd["matrix"] is True and bwd["matrix"] is True
    assert fwd["flops"] == 2 * CAUSAL * (DK + DR + DV)
    assert bwd["flops"] == 2 * CAUSAL * (3 * (DK + DR) + 2 * DV)
    assert fwd["flops"] < 2 * B * H * L * L * (DK + DR + DV) / 2 * 1.001
    head = 2 * B * H * L  # bytes of one bfloat16 column of every head's rows
    q, kv, kr, o, lse = head * (DK + DR), head * (DK + DV), 2 * B * L * DR, head * DV, 4 * B * H * L
    assert fwd["bytes"] == q + kv + kr + o + lse
    assert bwd["bytes"] == (q + kv + kr + o + 2 * lse) + (q + kv + 2 * head * DR)
    # FLOPs-bound: 7.0 and 18.1 ms at the peak, their bytes 0.7 and 1.2 ms
    assert roofline.kernel_min_seconds(fwd, PEAKS) == fwd["flops"] / 197e12
    assert roofline.kernel_min_seconds(bwd, PEAKS) == bwd["flops"] / 197e12


@pytest.mark.parametrize("layer", ["L0", "L3"])
def test_the_forward_is_one_pass_of_the_configurations_own_core(layer):
    """At `kanana2_30b.train`'s shapes the forward's FLOPs are one forward pass of ``flops/kanana2_30b.py``'s
    ``mla_scores`` + ``mla_values`` for the same layer, over the cell's two rows: both count the causal half."""
    cell, config = harness.load_cell("kanana2_30b.train")
    settings = harness.settings_for(cell, config, False)
    lm = settings["LM"]
    assert (lm["ATTN_HEADS"], lm["SEQ_LEN"], lm["QK_NOPE_DIM"], lm["QK_ROPE_DIM"], lm["V_HEAD_DIM"]) == (H, L, DK, DR, DV)
    rows = settings["TRAIN"]["BATCH_SIZE"]
    core = [x for x in files.load_module("flops", "kanana2_30b").layers(settings)
            if x["name"] in (f"{layer}.mla_scores", f"{layer}.mla_values")]
    assert len(core) == 2 and rows == B
    fwd = roofline.kernel_costs(hlo.kernel_calls(TEXT))["dtpu_causal_attn_fwd.13"]
    assert fwd["flops"] == 2.0 * rows * sum(x["macs"] for x in core)
