"""The reading of compiled HLO text by ``tools/hlo_dtypes.py``, on a
small module in the form the compiler prints: a weight converted to
bfloat16 before a loop, and products inside and outside the loop."""
import sys

import bench_paths  # noqa: F401  (first: the import path)

sys.path.insert(0, str(bench_paths.HERE / "tools"))
import hlo_dtypes  # noqa: E402

HLO = """\
HloModule jit_f, is_scheduled=true

%fused_computation (param_0.1: f32[8,1024], param_1.2: bf16[1024,1024]) -> f32[8,1024] {
  %param_0.1 = f32[8,1024]{1,0} parameter(0)
  %copy.1 = bf16[8,1024]{1,0} copy(%param_0.1)
  %param_1.2 = bf16[1024,1024]{1,0} parameter(1)
  ROOT %convolution.4 = f32[8,1024]{1,0} convolution(%copy.1, %param_1.2), dim_labels=bf_io->bf, metadata={op_name="jit(f)/while/body/moe/dot_general"}
}

%body (arg.2: (s32[], f32[8,1024], bf16[1024,1024])) -> (s32[], f32[8,1024], bf16[1024,1024]) {
  %arg.2 = (s32[], f32[8,1024]{1,0}, bf16[1024,1024]{1,0:S(1)}) parameter(0)
  %get-tuple-element.37 = bf16[1024,1024]{1,0:S(1)} get-tuple-element(%arg.2), index=2
  %bitcast.5 = bf16[1024,1024]{1,0:S(1)} bitcast(%get-tuple-element.37)
  %get-tuple-element.33 = f32[8,1024]{1,0} get-tuple-element(%arg.2), index=1
  %get-tuple-element.32 = s32[] get-tuple-element(%arg.2), index=0
  %fusion.182 = f32[8,1024]{1,0} fusion(%get-tuple-element.33, %bitcast.5), kind=kOutput, calls=%fused_computation, metadata={op_name="jit(f)/while/body/moe/dot_general"}
  ROOT %tuple.10 = (s32[], f32[8,1024]{1,0}, bf16[1024,1024]{1,0:S(1)}) tuple(%get-tuple-element.32, %fusion.182, %get-tuple-element.37)
}

%cond (arg.0: (s32[], f32[8,1024], bf16[1024,1024])) -> pred[] {
  %arg.0 = (s32[], f32[8,1024]{1,0}, bf16[1024,1024]{1,0:S(1)}) parameter(0)
  %constant.6 = s32[] constant(4)
  %get-tuple-element.20 = s32[] get-tuple-element(%arg.0), index=0
  ROOT %lt.3 = pred[] compare(%get-tuple-element.20, %constant.6), direction=LT
}

%wrapped_convert_computation (param_0.3: f32[1024,1024]) -> bf16[1024,1024] {
  %param_0.3 = f32[1024,1024]{1,0} parameter(0)
  ROOT %convert_element_type.11 = bf16[1024,1024]{1,0} convert(%param_0.3), metadata={op_name="jit(f)/while/body/moe/convert_element_type"}
}

ENTRY %main.4 (w.1: f32[1024,1024], x.1: f32[8,1024], h.1: f32[1024,32000]) -> f32[8,32000] {
  %w.1 = f32[1024,1024]{1,0} parameter(0), metadata={op_name="w"}
  %x.1 = f32[8,1024]{1,0} parameter(1), metadata={op_name="x"}
  %h.1 = f32[1024,32000]{1,0} parameter(2), metadata={op_name="h"}
  %constant.5 = s32[] constant(0)
  %convert.9 = bf16[1024,1024]{1,0:S(1)} fusion(%w.1), kind=kLoop, calls=%wrapped_convert_computation, metadata={op_name="jit(f)/while/body/moe/convert_element_type"}
  %tuple.8 = (s32[], f32[8,1024]{1,0}, bf16[1024,1024]{1,0:S(1)}) tuple(%constant.5, %x.1, %convert.9)
  %while.1 = (s32[], f32[8,1024]{1,0}, bf16[1024,1024]{1,0:S(1)}) while(%tuple.8), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"4"}}
  %gte.7 = f32[8,1024]{1,0} get-tuple-element(%while.1), index=1
  %copy.57 = bf16[1024,32000]{1,0} copy(%h.1), metadata={op_name="jit(f)/head/copy"}
  ROOT %dot.2 = f32[8,32000]{1,0} dot(%gte.7, %copy.57), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(f)/head/dot_general"}
}
"""


def test_products_and_converts_by_top_level_op():
    got = hlo_dtypes.analyse(HLO)
    # inside the loop the top-level op reads the float32 activations and
    # converts them itself; the weight comes as bfloat16, held in the
    # on-chip memory space S(1) from one micro-step to the next
    assert got["products"] == [
        {"op": "fusion.182", "in_loop": True,
         "scope": "jit(f)/while/body/moe/dot_general",
         "operands": ["bf16[8,1024]", "bf16[1024,1024]"],
         "reads": ["f32[8,1024]", "bf16[1024,1024] S(1)"],
         "result": "f32[8,1024]"},
        {"op": "dot.2", "in_loop": False, "scope": "jit(f)/head/dot_general",
         "operands": ["f32[8,1024]", "bf16[1024,32000]"],
         "reads": ["f32[8,1024]", "bf16[1024,32000]"],
         "result": "f32[8,32000]"}]
    # the weights' conversions run once per call, outside the loop, one
    # of them as a copy to the narrower type; the small per-step
    # conversion of the activations is not a weight's
    assert got["converts"] == [
        {"op": "convert.9", "in_loop": False,
         "scope": "jit(f)/while/body/moe/convert_element_type",
         "from": "f32[1024,1024]", "to": "bf16[1024,1024]"},
        {"op": "copy.57", "in_loop": False, "scope": "jit(f)/head/copy",
         "from": "f32[1024,32000]", "to": "bf16[1024,32000]"}]
