"""The grouped-product kernels' cost files price a call from its shapes, and `hlo.kernel_calls` finds
both kernels inside a loop's body: the expert layers run inside the scanned unit's ``while``."""

from benchmark import files, hlo, roofline

# A made text of a few lines: a ``while`` whose body calls each kernel once, with the operand order
# and the metadata of the calls in the compiled step of ``nemotron3_super.train`` (bodies cut).
TEXT = """HloModule jit_step_training

%body.1 (p: (s32[25], s32[1], bf16[6400,1024], bf16[8,1024,2688], bf16[6400,2688], s32[33])) -> (s32[25], s32[1], bf16[6400,1024], bf16[8,1024,2688], bf16[6400,2688], s32[33]) {
  %p = (s32[25]{0}, s32[1]{0}, bf16[6400,1024]{1,0}, bf16[8,1024,2688]{2,1,0}, bf16[6400,2688]{1,0}, s32[33]{0}) parameter(0)
  %expert.1 = s32[25]{0} get-tuple-element(%p), index=0
  %live.1 = s32[1]{0} get-tuple-element(%p), index=1
  %rows.1 = bf16[6400,1024]{1,0:T(8,128)(2,1)} get-tuple-element(%p), index=2
  %w.1 = bf16[8,1024,2688]{2,1,0:T(8,128)(2,1)} get-tuple-element(%p), index=3
  %d_out.1 = bf16[6400,2688]{1,0:T(8,128)(2,1)} get-tuple-element(%p), index=4
  %walk.1 = s32[33]{0} get-tuple-element(%p), index=5
  %dtpu_moe_gmm.2 = f32[6400,2688]{1,0:T(8,128)} custom-call(%expert.1, %live.1, %rows.1, %w.1), custom_call_target="tpu_custom_call", operand_layout_constraints={s32[25]{0}, s32[1]{0}, bf16[6400,1024]{1,0}, bf16[8,1024,2688]{2,1,0}}, frontend_attributes={kernel_metadata={}}, metadata={op_name="jit(step_training)/jvp(NemotronH)/U0/dtpu.moe_experts/dtpu.moe_experts/dtpu_moe_gmm/pallas_call" stack_frame_id=7}, backend_config={"custom_call_config":{"body":"..."}}
  %dtpu_moe_tgmm.1 = f32[8,1024,2688]{2,1,0:T(8,128)} custom-call(%walk.1, %walk.1, %walk.1, %rows.1, %d_out.1), custom_call_target="tpu_custom_call", operand_layout_constraints={s32[33]{0}, s32[33]{0}, s32[33]{0}, bf16[6400,1024]{1,0}, bf16[6400,2688]{1,0}}, frontend_attributes={kernel_metadata={}}, metadata={op_name="jit(step_training)/transpose(jvp(NemotronH))/U0/jvp(NemotronH)/U0/checkpoint/dtpu.moe_experts/dtpu.moe_experts/dtpu_moe_tgmm/pallas_call" stack_frame_id=3}, backend_config={"custom_call_config":{"body":"..."}}
  ROOT %next.1 = (s32[25]{0}, s32[1]{0}, bf16[6400,1024]{1,0}, bf16[8,1024,2688]{2,1,0}, bf16[6400,2688]{1,0}, s32[33]{0}) tuple(%expert.1, %live.1, %rows.1, %w.1, %d_out.1, %walk.1)
}

%cond.1 (p.1: (s32[25], s32[1], bf16[6400,1024], bf16[8,1024,2688], bf16[6400,2688], s32[33])) -> pred[] {
  %p.1 = (s32[25]{0}, s32[1]{0}, bf16[6400,1024]{1,0}, bf16[8,1024,2688]{2,1,0}, bf16[6400,2688]{1,0}, s32[33]{0}) parameter(0)
  ROOT %go.1 = pred[] constant(false)
}

ENTRY %main.1 (a: (s32[25], s32[1], bf16[6400,1024], bf16[8,1024,2688], bf16[6400,2688], s32[33])) -> (s32[25], s32[1], bf16[6400,1024], bf16[8,1024,2688], bf16[6400,2688], s32[33]) {
  %a = (s32[25]{0}, s32[1]{0}, bf16[6400,1024]{1,0}, bf16[8,1024,2688]{2,1,0}, bf16[6400,2688]{1,0}, s32[33]{0}) parameter(0)
  ROOT %while.1 = (s32[25]{0}, s32[1]{0}, bf16[6400,1024]{1,0}, bf16[8,1024,2688]{2,1,0}, bf16[6400,2688]{1,0}, s32[33]{0}) while(%a), condition=%cond.1, body=%body.1
}
"""

ROWS, K, N, HELD, BLOCKS = 6400, 1024, 2688, 8, 25


def test_kernel_calls_finds_both_kernels_inside_the_loop_with_operands_and_results():
    calls = hlo.kernel_calls(TEXT)
    assert set(calls) == {"dtpu_moe_gmm.2", "dtpu_moe_tgmm.1"}
    gmm, tgmm = calls["dtpu_moe_gmm.2"], calls["dtpu_moe_tgmm.1"]
    assert gmm["kernel"] == "dtpu_moe_gmm" and tgmm["kernel"] == "dtpu_moe_tgmm"
    assert gmm["operands"] == [("s32", (BLOCKS,)), ("s32", (1,)), ("bf16", (ROWS, K)), ("bf16", (HELD, K, N))]
    assert gmm["results"] == [("f32", (ROWS, N))]
    assert tgmm["operands"] == [("s32", (BLOCKS + HELD,))] * 3 + [("bf16", (ROWS, K)), ("bf16", (ROWS, N))]
    assert tgmm["results"] == [("f32", (HELD, K, N))]
    classes = hlo.classify(TEXT)
    assert classes["dtpu_moe_gmm.2"] == "kernel" and classes["dtpu_moe_tgmm.1"] == "kernel"


def test_the_cost_files_price_what_a_call_takes_whatever_the_routing():
    """The result written once: the products and the operands' reads follow the live blocks, which no
    shape tells, so they are a ceiling beside the cost and not in it (a share must not pass 100)."""
    costs = roofline.kernel_costs(hlo.kernel_calls(TEXT))
    gmm, tgmm = costs["dtpu_moe_gmm.2"], costs["dtpu_moe_tgmm.1"]
    assert gmm["matrix"] is True and tgmm["matrix"] is True
    assert gmm["flops"] == tgmm["flops"] == 0.0
    assert gmm["bytes"] == 4 * ROWS * N and tgmm["bytes"] == 4 * HELD * K * N
    peaks = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert roofline.kernel_min_seconds(gmm, peaks) == 4 * ROWS * N / 819e9


def test_the_transposed_product_is_priced_by_its_result():
    """The input gradient: rows [R, N] against w [held, K, N] transposed, a bfloat16 result [R, K]."""
    module = files.load_module("kernels", "dtpu_moe_gmm")
    operands = [("s32", (BLOCKS,)), ("s32", (1,)), ("bf16", (ROWS, N)), ("bf16", (HELD, K, N))]
    assert module.cost(operands, [("bf16", (ROWS, K))]) == {"flops": 0.0, "bytes": 2 * ROWS * K, "matrix": True}
