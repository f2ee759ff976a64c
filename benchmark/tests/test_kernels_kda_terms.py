"""The Kimi delta rule's within-chunk kernels' cost files price a call from its shapes, and `hlo.kernel_calls`
reads both calls, the forward's two results and the backward's five operands and three results."""

from benchmark import hlo, roofline

# A made text: the two calls as the compiled step of ``ling3_flash.train`` holds them for a group of 16 chunks of
# 32 heads (operands, results and metadata as the compiler printed them; bodies cut).
TEXT = """HloModule jit_step_scoped

ENTRY %main.1 (q: bf16[512,64,128], k: bf16[512,64,128], c: f32[512,64,128], dp: f32[512,64,64], dw: bf16[512,64,64]) -> (f32[512,64,64], bf16[512,64,128]) {
  %q.1 = bf16[512,64,128]{2,1,0} parameter(0)
  %k.1 = bf16[512,64,128]{2,1,0} parameter(1)
  %c.1 = f32[512,64,128]{2,1,0} parameter(2)
  %dp.1 = f32[512,64,64]{2,1,0} parameter(3)
  %dw.1 = bf16[512,64,64]{2,1,0} parameter(4)
  %dtpu_kda_terms.1 = (f32[512,64,64]{2,1,0:T(8,128)S(1)}, bf16[512,64,64]{2,1,0:T(8,128)(2,1)}) custom-call(%q.1, %k.1, %c.1), custom_call_target="tpu_custom_call", operand_layout_constraints={bf16[512,64,128]{2,1,0}, bf16[512,64,128]{2,1,0}, f32[512,64,128]{2,1,0}}, frontend_attributes={kernel_metadata={}}, metadata={op_name="jit(step_scoped)/jvp(dtpu.kda_scan)/dtpu_kda_terms/pallas_call" stack_frame_id=1}, backend_config={"custom_call_config":{"body":"..."}}
  %dtpu_kda_terms_bwd.1 = (bf16[512,64,128]{2,1,0:T(8,128)(2,1)}, bf16[512,64,128]{2,1,0:T(8,128)(2,1)}, f32[512,64,128]{2,1,0:T(8,128)}) custom-call(%q.1, %k.1, %c.1, %dp.1, %dw.1), custom_call_target="tpu_custom_call", operand_layout_constraints={bf16[512,64,128]{2,1,0}, bf16[512,64,128]{2,1,0}, f32[512,64,128]{2,1,0}, f32[512,64,64]{2,1,0}, bf16[512,64,64]{2,1,0}}, frontend_attributes={kernel_metadata={}}, metadata={op_name="jit(step_scoped)/transpose(jvp(dtpu.kda_scan))/dtpu_kda_terms_bwd/pallas_call" stack_frame_id=1}, backend_config={"custom_call_config":{"body":"..."}}
  %p.1 = f32[512,64,64]{2,1,0} get-tuple-element(%dtpu_kda_terms.1), index=0
  %dk.1 = bf16[512,64,128]{2,1,0} get-tuple-element(%dtpu_kda_terms_bwd.1), index=1
  ROOT %out.1 = (f32[512,64,64]{2,1,0}, bf16[512,64,128]{2,1,0}) tuple(%p.1, %dk.1)
}
"""

TILES, Q, K = 512, 64, 128
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
WIDE = lambda dtype: (dtype, (TILES, Q, K))
SQUARE = lambda dtype: (dtype, (TILES, Q, Q))


def test_kernel_calls_reads_both_calls_and_their_operands():
    calls = hlo.kernel_calls(TEXT)
    assert set(calls) == {"dtpu_kda_terms.1", "dtpu_kda_terms_bwd.1"}
    fwd, bwd = calls["dtpu_kda_terms.1"], calls["dtpu_kda_terms_bwd.1"]
    assert fwd["kernel"] == "dtpu_kda_terms" and bwd["kernel"] == "dtpu_kda_terms_bwd"
    assert fwd["operands"] == [WIDE("bf16"), WIDE("bf16"), WIDE("f32")]
    assert fwd["results"] == [SQUARE("f32"), SQUARE("bf16")]
    assert bwd["operands"] == [WIDE("bf16"), WIDE("bf16"), WIDE("f32"), SQUARE("f32"), SQUARE("bf16")]
    assert bwd["results"] == [WIDE("bf16"), WIDE("bf16"), WIDE("f32")]
    assert all(hlo.classify(TEXT)[name] == "kernel" for name in calls)


def test_the_cost_files_price_the_sub_chunks_products_once_and_the_bytes_once():
    """A tile's four sub-chunks of 16: their 32 rows of keys and queries against 16, 32, 48 and 64 keys of 128
    channels forward, twice that backward; operands in and results out once. The floor is the bytes': 0.056 and
    0.097 ms a call of 512 tiles."""
    costs = roofline.kernel_costs(hlo.kernel_calls(TEXT))
    fwd, bwd = costs["dtpu_kda_terms.1"], costs["dtpu_kda_terms_bwd.1"]
    macs = 32 * (16 + 32 + 48 + 64) * K
    assert fwd["matrix"] is True and bwd["matrix"] is True
    assert fwd["flops"] == 2 * TILES * macs and bwd["flops"] == 2 * 2 * TILES * macs
    wide, square = TILES * Q * K, TILES * Q * Q
    assert fwd["bytes"] == (2 + 2 + 4) * wide + (4 + 2) * square
    assert bwd["bytes"] == (2 + 2 + 4) * wide + (4 + 2) * square + (2 + 2 + 4) * wide
    assert roofline.kernel_min_seconds(fwd, PEAKS) == fwd["bytes"] / 819e9
    assert roofline.kernel_min_seconds(bwd, PEAKS) == bwd["bytes"] / 819e9


def test_a_shorter_chunk_counts_its_own_sub_chunks():
    from benchmark import files

    module = files.load_module("kernels", "dtpu_kda_terms")
    wide = ("bf16", (3, 32, 128))
    assert module.cost([wide, wide, ("f32", (3, 32, 128))], [])["flops"] == 2 * 3 * 32 * (16 + 32) * 128
