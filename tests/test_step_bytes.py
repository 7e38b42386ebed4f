"""tools/step_bytes.py: the count of a step's HBM bytes by kind of op, on a
small written-out program (the real ones compile for a minute or two and
are read in PERF.md §5)."""
import importlib.util
import os

import pytest

_spec = importlib.util.spec_from_file_location(
    "step_bytes", os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "step_bytes.py"))
step_bytes = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(step_bytes)

HLO = """HloModule jit_step, is_scheduled=true

%fused_conv (p0: bf16[8,4,4,128], p1: f32[1,1,64,64]) -> bf16[8,4,4,128] {
  %p0 = bf16[8,4,4,128]{3,0,2,1} parameter(0)
  %p1 = f32[1,1,64,64]{3,2,1,0} parameter(1)
  ROOT %convolution.1 = bf16[8,4,4,128]{3,0,2,1} convolution(%p0, %p1), window={size=1x1}
}

%fused_sum (p0: bf16[8,4,4,128]) -> f32[64] {
  %p0 = bf16[8,4,4,128]{3,0,2,1} parameter(0)
  ROOT %reduce.1 = f32[64]{0} reduce(%p0), dimensions={0,1,2}
}

%fused_relu (p0: bf16[8,4,4,128]) -> bf16[8,4,4,128] {
  %p0 = bf16[8,4,4,128]{3,0,2,1} parameter(0)
  ROOT %maximum.1 = bf16[8,4,4,128]{3,0,2,1} maximum(%p0, %p0)
}

%fused_sgd (p0: f32[1,1,64,64], p1: f32[1,1,64,64]) -> f32[1,1,64,64] {
  %p0 = f32[1,1,64,64]{3,2,1,0} parameter(0)
  %p1 = f32[1,1,64,64]{3,2,1,0} parameter(1)
  ROOT %subtract.1 = f32[1,1,64,64]{3,2,1,0} subtract(%p0, %p1)
}

%fused_mask (p0: bf16[8,4,4,128]) -> (bf16[8,4,4,128], pred[8,4,4,128]) {
  %p0 = bf16[8,4,4,128]{3,0,2,1} parameter(0)
  %zero = bf16[] constant(0)
  %zeros = bf16[8,4,4,128]{3,0,2,1} broadcast(%zero), dimensions={}
  %compare.1 = pred[8,4,4,128]{3,0,2,1} compare(%p0, %zeros), direction=GT
  ROOT %tuple.2 = (bf16[8,4,4,128]{3,0,2,1}, pred[8,4,4,128]{3,0,2,1}) tuple(%p0, %compare.1)
}

%fused_select (p0: bf16[8,4,4,128], p1: pred[8,4,4,128]) -> bf16[8,4,4,128] {
  %p0 = bf16[8,4,4,128]{3,0,2,1} parameter(0)
  %p1 = pred[8,4,4,128]{3,0,2,1} parameter(1)
  %zero = bf16[] constant(0)
  %zeros = bf16[8,4,4,128]{3,0,2,1} broadcast(%zero), dimensions={}
  ROOT %select.1 = bf16[8,4,4,128]{3,0,2,1} select(%p1, %p0, %zeros)
}

ENTRY %main.1 (x: bf16[8,4,4,128], w: f32[1,1,64,64]) -> (bf16[8,4,4,128], f32[1,1,64,64]) {
  %x = bf16[8,4,4,128]{3,2,1,0:T(8,128)(2,1)} parameter(0)
  %w = f32[1,1,64,64]{3,2,1,0:T(8,128)} parameter(1)
  %copy.1 = bf16[8,4,4,128]{3,0,2,1:T(8,128)(2,1)} copy(%x)
  %fusion.1 = bf16[8,4,4,128]{3,0,2,1:T(8,128)(2,1)} fusion(%copy.1, %w), kind=kOutput, calls=%fused_conv
  %copy-start.1 = (bf16[8,4,4,128]{3,0,2,1:T(8,128)(2,1)S(1)}, bf16[8,4,4,128]{3,0,2,1:T(8,128)(2,1)}, u32[]{:S(2)}) copy-start(%fusion.1)
  %copy-done.1 = bf16[8,4,4,128]{3,0,2,1:T(8,128)(2,1)S(1)} copy-done(%copy-start.1)
  %fusion.2 = f32[64]{0:T(128)} fusion(%copy-done.1), kind=kLoop, calls=%fused_sum
  %bn_tail.3 = bf16[128,128]{1,0:T(8,128)(2,1)} custom-call(%fusion.1), custom_call_target="tpu_custom_call"
  %custom-call.4 = bf16[8,4,4,128]{3,0,2,1:T(8,128)(2,1)} custom-call(%fusion.1), custom_call_target="ConcatBitcast"
  %fusion.3 = bf16[8,4,4,128]{3,0,2,1:T(8,128)(2,1)} fusion(%fusion.1), kind=kLoop, calls=%fused_relu
  %fusion.4 = f32[1,1,64,64]{3,2,1,0:T(8,128)} fusion(%w, %w), kind=kLoop, calls=%fused_sgd
  %all-reduce.5 = f32[64]{0:T(128)} all-reduce(%fusion.2), replica_groups={}
  %fusion.6 = (bf16[8,4,4,128]{3,0,2,1:T(8,128)(2,1)}, pred[8,4,4,128]{3,0,2,1:T(8,128)(4,1)}) fusion(%fusion.1), kind=kLoop, calls=%fused_mask
  %get-tuple-element.1 = bf16[8,4,4,128]{3,0,2,1:T(8,128)(2,1)} get-tuple-element(%fusion.6), index=0
  %get-tuple-element.2 = pred[8,4,4,128]{3,0,2,1:T(8,128)(4,1)} get-tuple-element(%fusion.6), index=1
  %copy-start.2 = (pred[8,4,4,128]{3,0,2,1:T(8,128)(4,1)S(1)}, pred[8,4,4,128]{3,0,2,1:T(8,128)(4,1)}, u32[]{:S(2)}) copy-start(%get-tuple-element.2)
  %copy-done.2 = pred[8,4,4,128]{3,0,2,1:T(8,128)(4,1)S(1)} copy-done(%copy-start.2)
  %fusion.7 = bf16[8,4,4,128]{3,0,2,1:T(8,128)(2,1)} fusion(%get-tuple-element.1, %copy-done.2), kind=kLoop, calls=%fused_select
  %fusion.8 = bf16[8,4,4,128]{3,0,2,1:T(8,128)(2,1)} fusion(%get-tuple-element.1, %get-tuple-element.2), kind=kLoop, calls=%fused_select
  %compare.9 = pred[64]{0:T(128)(4,1)} compare(%fusion.2, %fusion.2), direction=GT
  ROOT %tuple.1 = (bf16[8,4,4,128]{3,0,2,1:T(8,128)(2,1)}, f32[1,1,64,64]{3,2,1,0:T(8,128)}) tuple(%fusion.3, %fusion.4)
}
"""
ACT = 8 * 4 * 4 * 128 * 2  # the activation, bf16
W = 64 * 64 * 4
MASK = 8 * 4 * 4 * 128  # the activation's mask, a byte an element


@pytest.mark.parametrize("name, kind, moved", [
    ("copy.1", "copy", 2 * ACT),
    ("fusion.1", "convolution fusion", 2 * ACT + W),
    ("copy-start.1", "async copy/slice", ACT + 4),  # once, and its context
    ("fusion.2", "reduction fusion", ACT + 64 * 4),
    ("bn_tail.3", "custom call", 2 * ACT),
    ("fusion.3", "elementwise fusion", 2 * ACT),
    ("fusion.4", "optimizer", 3 * W),
    ("all-reduce.5", "all-reduce", 2 * 64 * 4),
    ("fusion.6", "elementwise fusion", 2 * ACT + MASK),  # two results
    ("copy-start.2", "async copy/slice", MASK + 4),
    ("fusion.8", "elementwise fusion", 2 * ACT + MASK),
])
def test_each_instruction_is_charged_its_operands_and_result(
        name, kind, moved):
    assert step_bytes.classify(HLO, {W})[name] == (kind, moved)


def test_what_moves_nothing_is_left_out_and_traced_time_joins_by_name():
    got = step_bytes.classify(HLO, {W})
    for free in ("x", "w", "tuple.1", "copy-done.1", "custom-call.4"):
        assert free not in got
    seconds = {"fusion.1": 2e-3, "copy-done.1": 1e-3, "copy-start.1": 1e-5,
               "fusion.999": 5e-4}
    table = step_bytes.table(got, seconds)
    assert table["kinds"]["convolution fusion"] == {
        "ops": 1, "gb": (2 * ACT + W) / 1e9, "ms": 2.0}
    assert table["kinds"]["async copy/slice"]["ms"] == pytest.approx(1.01)
    assert table["unmatched_ms"] == pytest.approx(0.5)  # another program's
    assert "reshape/transpose" not in table["kinds"]
    assert table["largest"][0] == [
        "fusion.1", "convolution fusion", (2 * ACT + W) / 1e6, 2.0]


def test_a_mask_stored_beside_what_it_masks_is_counted_where_it_moves():
    """`pred_gb`: written by the fusion that makes it, once more by the
    async copy's destination, read by each fusion that selects on it; a
    channel's worth of `pred` is under the floor."""
    assert step_bytes.pred_bytes(HLO, over=1000) == 4 * MASK
    assert step_bytes.pred_bytes(HLO, over=10) == 4 * MASK + 64
    assert step_bytes.pred_bytes(HLO) == 0  # nothing here is over 1 MB


MOVERS = """HloModule jit_step, is_scheduled=true

ENTRY %main.2 (q: bf16[2,128,60]) -> f32[2,64,6,2,10] {
  %q = bf16[2,128,60]{2,1,0:T(8,128)(2,1)} parameter(0)
  %reshape.1 = bf16[2,2,64,6,10]{4,3,2,1,0:T(8,128)(2,1)} reshape(%q), metadata={op_name="jit(step)/block_0/mixer/reshape"}
  %copy.1 = bf16[2,2,64,6,10]{4,2,3,0,1:T(8,128)(2,1)} copy(%reshape.1), metadata={op_name="jit(step)/block_0/mixer/transpose"}
  %bitcast.1 = bf16[2,2,6,64,10]{4,3,2,1,0:T(8,128)(2,1)} bitcast(%copy.1)
  %convert.1 = f32[2,2,6,64,10]{4,3,2,1,0:T(8,128)} convert(%bitcast.1), metadata={op_name="jit(step)/block_0/mixer/gated_delta/convert_element_type"}
  %transpose.1 = f32[2,64,6,2,10]{4,3,2,1,0:T(8,128)} transpose(%convert.1), dimensions={0,3,2,1,4}, metadata={op_name="jit(step)/block_0/mixer/gated_delta/delta_inverse/transpose"}
  ROOT %copy.2 = f32[2,64,6,2,10]{3,4,2,1,0:T(8,128)} copy(%transpose.1), metadata={op_name="jit(step)/block_1/mlp/transpose"}
}
"""
Q = 2 * 128 * 60  # elements


def test_movers_are_counted_by_the_dtype_they_move():
    """`mover_gb`: `copy`, `reshape` and `transpose` of the entry
    computation read what they write; a `bitcast` moves nothing and a
    `convert` is no mover (PERF.md §6, PR 39)."""
    assert step_bytes.mover_bytes(MOVERS) == {
        "bf16": 2 * 2 * (2 * Q), "f32": 2 * 2 * (4 * Q)}
    assert step_bytes.mover_bytes(HLO) == {"bf16": 2 * ACT}  # `copy.1`


def test_scopes_take_each_instruction_once_by_the_first_that_matches():
    got = step_bytes.classify(MOVERS)
    seconds = {"copy.1": 1e-3, "transpose.1": 2e-3, "convert.1": 4e-3}
    rows = step_bytes.by_scope(
        MOVERS, got, ["/delta_inverse/", "/gated_delta/", r"block_\d/mixer/"],
        seconds)
    assert rows["/delta_inverse/"] == {
        "ops": 1, "gb": 8 * Q / 1e9, "ms": 2.0,
        "mover_ops": 1, "mover_gb": 8 * Q / 1e9, "mover_ms": 2.0}
    # the convert: in the scope, not a mover
    assert rows["/gated_delta/"] == {
        "ops": 1, "gb": 6 * Q / 1e9, "ms": 4.0,
        "mover_ops": 0, "mover_gb": 0, "mover_ms": 0}
    assert rows[r"block_\d/mixer/"]["mover_ops"] == 2
    assert rows[r"block_\d/mixer/"]["mover_ms"] == 1.0
    assert rows[""]["mover_gb"] == 8 * Q / 1e9  # `copy.2`: none of them
    assert sum(r["ops"] for r in rows.values()) == len(got) == 5
