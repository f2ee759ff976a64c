"""Op classes come from what an instruction contains in the compiled HLO, never from its name."""

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
