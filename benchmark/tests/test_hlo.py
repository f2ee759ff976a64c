"""Op classes come from what an instruction contains in the compiled HLO, never from its name."""

import pytest

from benchmark import hlo

TEXT = """HloModule jit_step, is_scheduled=true

%fused_computation.1 (param_0: bf16[8,8], param_1: bf16[8,8]) -> (f32[8], bf16[8,8]) {
  %param_0 = bf16[8,8]{1,0} parameter(0)
  %param_1 = bf16[8,8]{1,0} parameter(1)
  %convolution.9 = bf16[8,8]{1,0} convolution(%param_0, %param_1), window={size=1x1}, dim_labels=bf_io->bf
  %reduce.1 = f32[8]{0} reduce(%convolution.9, %param_0), dimensions={0}, to_apply=%add
  ROOT %tuple.1 = (f32[8]{0}, bf16[8,8]{1,0}) tuple(%reduce.1, %convolution.9)
}

%add (x: f32[], y: f32[]) -> f32[] {
  %x = f32[] parameter(0)
  %y = f32[] parameter(1)
  ROOT %add.1 = f32[] add(%x, %y)
}

%fused_computation.2 (param_0.1: bf16[8,8]) -> bf16[8,8] {
  %param_0.1 = bf16[8,8]{1,0} parameter(0)
  ROOT %multiply.1 = bf16[8,8]{1,0} multiply(%param_0.1, %param_0.1)
}

ENTRY %main.1_spmd (p0: bf16[8,8], p1: bf16[8,8]) -> bf16[8,8] {
  %p0 = bf16[8,8]{1,0} parameter(0)
  %p1 = bf16[8,8]{1,0} parameter(1)
  %multiply_reduce_fusion = (f32[8]{0}, /*index=1*/bf16[8,8]{1,0}) fusion(%p0, %p1), kind=kOutput, calls=%fused_computation.1
  %convolution_like_name = bf16[8,8]{1,0} fusion(%p0), kind=kLoop, calls=%fused_computation.2
  %all-reduce.3 = bf16[8,8]{1,0} all-reduce(%convolution_like_name), replica_groups={{0,1}}, to_apply=%add
  %dot.4 = bf16[8,8]{1,0} dot(%p0, %p1), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %copy.5 = bf16[8,8]{1,0} copy(%dot.4)
}
"""


def test_classes_follow_contents_not_names():
    classes = hlo.classify(TEXT)
    assert classes["multiply_reduce_fusion"] == "mxu"  # holds a convolution, named after neither
    assert classes["convolution_like_name"] == "vector"  # named like one, holds a multiply
    assert classes["all-reduce.3"] == "collective"
    assert classes["dot.4"] == "mxu"
    assert classes["copy.5"] == "vector"


def test_tuple_typed_instruction_parses():
    parsed = hlo.parse(TEXT)
    assert parsed["multiply_reduce_fusion"][0] == "fusion"
    assert parsed["multiply_reduce_fusion"][1] == ("fused_computation.1",)


# The two kernel calls of ``block11``/``block0`` and the ops that feed them, copied from the kept step text of
# ``vit_b16.train`` (PR 27's traced run on the chip), the Mosaic body in ``backend_config`` cut to "...".
KERNEL_TEXT = """HloModule jit_step_training, is_scheduled=true

ENTRY %main.1_spmd (p0: bf16[128,197,2304], p1: bf16[128,197,768], p2: f32[128,197,12]) -> bf16[128,197,2304] {
  %convolution_add_fusion.23 = bf16[128,197,2304]{2,1,0:T(8,128)(2,1)} parameter(0)
  %fusion.1017 = bf16[128,197,768]{2,1,0:T(8,128)(2,1)} parameter(1)
  %p2 = f32[128,197,12]{2,1,0:T(8,128)} parameter(2)
  %dtpu_attn_fwd.12 = (bf16[128,197,768]{2,1,0:T(8,128)(2,1)}, f32[128,197,12]{2,1,0:T(8,128)}) custom-call(%convolution_add_fusion.23), custom_call_target="tpu_custom_call", operand_layout_constraints={bf16[128,197,2304]{2,1,0}}, frontend_attributes={kernel_metadata={}}, metadata={op_name="jit(step_training)/jvp(ViT)/block0/attn/jit(_self_attn_fwd_call)/dtpu_attn_fwd/pallas_call" stack_frame_id=18}, backend_config={"flag_configs":[],"scoped_memory_configs":[],"custom_call_config":{"body":"..."}}
  %custom-call.106 = bf16[128,197,768]{2,1,0:T(8,128)(2,1)S(1)} custom-call(%fusion.1017), custom_call_target="ConcatBitcast", backend_config={"flag_configs":[],"scoped_memory_configs":[],"used_scoped_memory_configs":[],"aliasing_operands":{"lists":[]}}
  %custom-call.203 = f32[128,197,12]{2,1,0:T(8,128)S(1)} custom-call(%p2), custom_call_target="ConcatBitcast", backend_config={"flag_configs":[],"scoped_memory_configs":[],"used_scoped_memory_configs":[],"aliasing_operands":{"lists":[]}}
  ROOT %dtpu_attn_bwd.12 = bf16[128,197,2304]{2,1,0:T(8,128)(2,1)} custom-call(%convolution_add_fusion.23, %custom-call.106, %fusion.1017, %custom-call.203), custom_call_target="tpu_custom_call", operand_layout_constraints={bf16[128,197,2304]{2,1,0}, bf16[128,197,768]{2,1,0}, bf16[128,197,768]{2,1,0}, f32[128,197,12]{2,1,0}}, frontend_attributes={kernel_metadata={}}, metadata={op_name="jit(step_training)/transpose(jvp(ViT))/block11/attn/jit(_self_attn_bwd_call)/dtpu_attn_bwd/pallas_call" stack_frame_id=49}, backend_config={"flag_configs":[],"scoped_memory_configs":[],"custom_call_config":{"body":"..."}}
}
"""


def test_a_mosaic_custom_call_is_a_kernel_with_its_name_and_shapes():
    classes = hlo.classify(KERNEL_TEXT)
    assert classes["dtpu_attn_fwd.12"] == "kernel" and classes["dtpu_attn_bwd.12"] == "kernel"
    assert classes["custom-call.106"] == "vector"  # another target: XLA's own, no kernel
    calls = hlo.kernel_calls(KERNEL_TEXT)
    assert set(calls) == {"dtpu_attn_fwd.12", "dtpu_attn_bwd.12"}
    fwd, bwd = calls["dtpu_attn_fwd.12"], calls["dtpu_attn_bwd.12"]
    assert fwd["kernel"] == "dtpu_attn_fwd" and bwd["kernel"] == "dtpu_attn_bwd"
    assert fwd["operands"] == [("bf16", (128, 197, 2304))]
    assert fwd["results"] == [("bf16", (128, 197, 768)), ("f32", (128, 197, 12))]
    assert bwd["operands"] == [("bf16", (128, 197, 2304)), ("bf16", (128, 197, 768)),
                               ("bf16", (128, 197, 768)), ("f32", (128, 197, 12))]
    assert bwd["results"] == [("bf16", (128, 197, 2304))]
    assert hlo.kernel_calls(TEXT) == {}  # a step with no kernel


def test_a_kernel_call_without_a_name_cannot_be_priced():
    nameless = KERNEL_TEXT.replace("/dtpu_attn_fwd/pallas_call", "/dtpu_attn_fwd")
    with pytest.raises(ValueError, match="dtpu_attn_fwd.12"):
        hlo.kernel_calls(nameless)
