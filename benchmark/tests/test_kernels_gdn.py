"""The chunk inverse's kernels' cost files price a call from its shapes, and `hlo.kernel_calls` finds both
inside the scanned unit's ``while`` and inside the group's checkpoint: the delta-net layers run there."""

from benchmark import hlo, roofline

# A made text of a few lines: a ``while`` whose body calls each kernel, with the operands and the metadata of
# the calls in the compiled step of ``qwen3_next.train`` (forward, recomputed and backward; bodies cut).
TEXT = """HloModule jit_step_training

%body.1 (p: (f32[4096,64,64], f32[4096,64,64])) -> (f32[4096,64,64], f32[4096,64,64]) {
  %p = (f32[4096,64,64]{2,1,0}, f32[4096,64,64]{2,1,0}) parameter(0)
  %a.1 = f32[4096,64,64]{2,1,0:T(8,128)} get-tuple-element(%p), index=0
  %d_t.1 = f32[4096,64,64]{2,1,0:T(8,128)} get-tuple-element(%p), index=1
  %dtpu_gdn_inverse.1 = f32[4096,64,64]{2,1,0:T(8,128)} custom-call(%a.1), custom_call_target="tpu_custom_call", operand_layout_constraints={f32[4096,64,64]{2,1,0}}, frontend_attributes={kernel_metadata={}}, metadata={op_name="jit(step_training)/jvp(Qwen3Next)/while/body/closed_call/U0/closed_call/while/body/closed_call/checkpoint/dtpu.gdn_scan/dtpu_gdn_inverse/pallas_call" stack_frame_id=7}, backend_config={"custom_call_config":{"body":"..."}}
  %dtpu_gdn_inverse.2 = f32[4096,64,64]{2,1,0:T(8,128)} custom-call(%a.1), custom_call_target="tpu_custom_call", operand_layout_constraints={f32[4096,64,64]{2,1,0}}, frontend_attributes={kernel_metadata={}}, metadata={op_name="jit(step_training)/transpose(jvp(Qwen3Next))/while/body/closed_call/U0/U0/checkpoint/while/body/closed_call/checkpoint/rematted_computation/dtpu.gdn_scan/dtpu_gdn_inverse/pallas_call" stack_frame_id=7}, backend_config={"custom_call_config":{"body":"..."}}
  %dtpu_gdn_inverse_bwd.1 = f32[4096,64,64]{2,1,0:T(8,128)} custom-call(%dtpu_gdn_inverse.2, %d_t.1), custom_call_target="tpu_custom_call", operand_layout_constraints={f32[4096,64,64]{2,1,0}, f32[4096,64,64]{2,1,0}}, frontend_attributes={kernel_metadata={}}, metadata={op_name="jit(step_training)/transpose(jvp(Qwen3Next))/while/body/closed_call/U0/U0/checkpoint/while/body/closed_call/checkpoint/dtpu.gdn_scan/dtpu_gdn_inverse_bwd/pallas_call" stack_frame_id=3}, backend_config={"custom_call_config":{"body":"..."}}
  ROOT %next.1 = (f32[4096,64,64]{2,1,0}, f32[4096,64,64]{2,1,0}) tuple(%dtpu_gdn_inverse.1, %dtpu_gdn_inverse_bwd.1)
}

%cond.1 (p.1: (f32[4096,64,64], f32[4096,64,64])) -> pred[] {
  %p.1 = (f32[4096,64,64]{2,1,0}, f32[4096,64,64]{2,1,0}) parameter(0)
  ROOT %go.1 = pred[] constant(false)
}

ENTRY %main.1 (a: (f32[4096,64,64], f32[4096,64,64])) -> (f32[4096,64,64], f32[4096,64,64]) {
  %a = (f32[4096,64,64]{2,1,0}, f32[4096,64,64]{2,1,0}) parameter(0)
  ROOT %while.1 = (f32[4096,64,64]{2,1,0}, f32[4096,64,64]{2,1,0}) while(%a), condition=%cond.1, body=%body.1
}
"""

TILES, Q = 4096, 64
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_kernel_calls_finds_both_kernels_inside_the_loop_and_the_checkpoint():
    calls = hlo.kernel_calls(TEXT)
    assert set(calls) == {"dtpu_gdn_inverse.1", "dtpu_gdn_inverse.2", "dtpu_gdn_inverse_bwd.1"}
    tile = ("f32", (TILES, Q, Q))
    for name in ("dtpu_gdn_inverse.1", "dtpu_gdn_inverse.2"):
        assert calls[name]["kernel"] == "dtpu_gdn_inverse"
        assert calls[name]["operands"] == [tile] and calls[name]["results"] == [tile]
    bwd = calls["dtpu_gdn_inverse_bwd.1"]
    assert bwd["kernel"] == "dtpu_gdn_inverse_bwd" and bwd["operands"] == [tile, tile] and bwd["results"] == [tile]
    classes = hlo.classify(TEXT)
    assert all(classes[name] == "kernel" for name in calls)


def test_the_cost_files_price_the_products_once_and_the_tiles_bytes_once():
    """Ten float32 products a tile forward (five squarings, five with the factors), two backward; ``a`` in and
    ``T`` out, or ``T`` and ``dT`` in and ``da`` out. The floor is the bytes': 0.16 and 0.25 ms a call."""
    costs = roofline.kernel_costs(hlo.kernel_calls(TEXT))
    fwd, bwd = costs["dtpu_gdn_inverse.1"], costs["dtpu_gdn_inverse_bwd.1"]
    assert fwd == costs["dtpu_gdn_inverse.2"] and fwd["matrix"] is True and bwd["matrix"] is True
    assert fwd["flops"] == 10 * TILES * 2 * Q ** 3 and bwd["flops"] == 2 * TILES * 2 * Q ** 3
    assert fwd["bytes"] == 2 * 4 * TILES * Q * Q and bwd["bytes"] == 3 * 4 * TILES * Q * Q
    assert roofline.kernel_min_seconds(fwd, PEAKS) == fwd["bytes"] / 819e9   # 0.164 ms against 0.109 of products
    assert roofline.kernel_min_seconds(bwd, PEAKS) == bwd["bytes"] / 819e9


def test_a_smaller_chunk_counts_its_own_squarings():
    from benchmark import files

    module = files.load_module("kernels", "dtpu_gdn_inverse")
    tile = ("f32", (12, 16, 16))
    assert module.cost([tile], [tile])["flops"] == 6 * 12 * 2 * 16 ** 3   # three squarings, three products
