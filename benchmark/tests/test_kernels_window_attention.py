"""The window pair's cost files price a call from its shapes over the band, ``Σ_i min(i + 1, 512)`` key positions
a head, and `hlo.kernel_calls` reads a windowed call's operands inside the scanned unit's ``while``: the window
layers of ``laguna_s21.train`` run there. The forward's count is one pass of the configuration's own
``swa_scores`` + ``swa_values``, so that ``kernel_roofline_pct`` never reads the causal half against a kernel that
skips all but the band."""

import pytest

from benchmark import files, harness, hlo, roofline

B, H, G, L, D = 1, 72, 8, 8192, 128
Q = f"bf16[{B},{H},{L},{D}]"
KV = f"bf16[{B},{G},{L},{2 * D}]"
O = Q
LSE = f"f32[{B},{H},1,{L}]"
STATS = f"f32[{B},{H},2,{L}]"
DKV = f"f32[{B},{H},{L},{2 * D}]"


def _call(name, results, operands, where):
    names = ", ".join(f"%{n}" for n, _ in operands)
    constraints = ", ".join(t for _, t in operands)
    return (f'  %{name} = ({", ".join(results)}) custom-call({names}), custom_call_target="tpu_custom_call", '
            f'operand_layout_constraints={{{constraints}}}, frontend_attributes={{kernel_metadata={{}}}}, '
            f'metadata={{op_name="jit(step_scoped)/{where}/dtpu.causal_attn/dtpu.window_attn/{name.split(".")[0]}/pallas_call" '
            f'stack_frame_id=1}}, backend_config={{"custom_call_config":{{"body":"..."}}}}')


FWD_IN = [("q", Q), ("kv", KV)]
BWD_IN = FWD_IN + [("do", O), ("stats", STATS)]
TEXT = "\n".join([
    "HloModule jit_step_scoped", "",
    f"%body.1 (p: ({O}, {O})) -> ({O}, {O}) {{",
    f"  %p = ({O}{{3,2,1,0}}, {O}{{3,2,1,0}}) parameter(0)",
    *(f"  %{n} = {t}{{3,2,1,0}} constant({{0}})" for n, t in BWD_IN),
    _call("dtpu_window512_attn_fwd.3", [O, LSE], FWD_IN, "jvp(Laguna)/while/body/closed_call/U0"),
    _call("dtpu_window512_attn_bwd.4", [Q, DKV], BWD_IN, "transpose(jvp(Laguna))/while/body/closed_call/U0/checkpoint"),
    f"  ROOT %next.1 = ({O}{{3,2,1,0}}, {O}{{3,2,1,0}}) tuple(%do, %do)",
    "}", "",
    f"%cond.1 (p.1: ({O}, {O})) -> pred[] {{",
    f"  %p.1 = ({O}{{3,2,1,0}}, {O}{{3,2,1,0}}) parameter(0)",
    "  ROOT %go.1 = pred[] constant(false)",
    "}", "",
    f"ENTRY %main.1 (a: ({O}, {O})) -> ({O}, {O}) {{",
    f"  %a = ({O}{{3,2,1,0}}, {O}{{3,2,1,0}}) parameter(0)",
    f"  ROOT %while.1 = ({O}{{3,2,1,0}}, {O}{{3,2,1,0}}) while(%a), condition=%cond.1, body=%body.1",
    "}", ""])

BAND = B * H * (512 * 513 // 2 + (L - 512) * 512)


def test_kernel_calls_reads_a_windowed_calls_operands_inside_the_loop():
    calls = hlo.kernel_calls(TEXT)
    assert {name: call["kernel"] for name, call in calls.items()} == {
        "dtpu_window512_attn_fwd.3": "dtpu_window512_attn_fwd", "dtpu_window512_attn_bwd.4": "dtpu_window512_attn_bwd"}
    fwd, bwd = calls["dtpu_window512_attn_fwd.3"], calls["dtpu_window512_attn_bwd.4"]
    assert fwd["operands"] == [("bf16", (B, H, L, D)), ("bf16", (B, G, L, 2 * D))]
    assert fwd["results"] == [("bf16", (B, H, L, D)), ("f32", (B, H, 1, L))]
    assert len(bwd["operands"]) == 4 and bwd["results"] == [("bf16", (B, H, L, D)), ("f32", (B, H, L, 2 * D))]


def test_the_cost_files_price_the_band_against_hand_counts():
    """Forward: scores at dk and values at dv, a position of the band; backward: the scores again, dP and dV at dv,
    dQ and dK at dk. Bytes: operands and results once."""
    costs = roofline.kernel_costs(hlo.kernel_calls(TEXT))
    fwd, bwd = costs["dtpu_window512_attn_fwd.3"], costs["dtpu_window512_attn_bwd.4"]
    assert fwd["matrix"] is True and bwd["matrix"] is True
    assert fwd["flops"] == 2 * BAND * (D + D) == 2 * 72 * 4_063_488 * 256
    assert bwd["flops"] == 2 * BAND * (3 * D + 2 * D)
    head = 2 * B * H * L  # bytes of one bfloat16 column of every head's rows
    q, kv, o, lse, stats = head * D, 2 * B * G * L * 2 * D, head * D, 4 * B * H * L, 8 * B * H * L
    assert fwd["bytes"] == q + kv + o + lse
    assert bwd["bytes"] == (q + kv + o + stats) + (q + 2 * head * 2 * D)
    # FLOPs-bound: 0.76 and 1.90 ms at the peak
    assert roofline.kernel_min_seconds(fwd, {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}) == fwd["flops"] / 197e12


def test_rows_under_the_window_count_the_triangle_alone():
    fwd = files.load_module("kernels", "dtpu_window512_attn_fwd")
    assert fwd.band_positions((1, 1, 300, D)) == 300 * 301 // 2
    assert fwd.band_positions((2, 3, 512, D)) == 6 * 512 * 513 // 2


@pytest.mark.parametrize("layer", ["L1", "L3"])
def test_the_forward_is_one_pass_of_the_configurations_own_core(layer):
    """At `laguna_s21.train`'s shapes the forward's FLOPs are one forward pass of ``flops/laguna_s21.py``'s
    ``swa_scores`` + ``swa_values`` for the same layer: both count the band."""
    cell, config = harness.load_cell("laguna_s21.train")
    settings = harness.settings_for(cell, config, False)
    lm = settings["LM"]
    assert (lm["WINDOW_HEADS"], lm["KV_HEADS"], lm["SEQ_LEN"], lm["HEAD_DIM"], lm["WINDOW"]) == (H, G, L, D, 512)
    core = [x for x in files.load_module("flops", "laguna_s21").layers(settings)
            if x["name"] in (f"{layer}.swa_scores", f"{layer}.swa_values")]
    assert len(core) == 2 and settings["TRAIN"]["BATCH_SIZE"] == B
    fwd = roofline.kernel_costs(hlo.kernel_calls(TEXT))["dtpu_window512_attn_fwd.3"]
    assert fwd["flops"] == 2.0 * B * sum(x["macs"] for x in core)
