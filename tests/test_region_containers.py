"""A ``lax.scan`` or ``lax.cond`` in a step is a ``while`` / ``conditional``
instruction whose device event spans its body's events, which the device
line lists too. The region table and ``newest_step_regions`` count the
body's operations and leave the container out, so the rows still sum to
the busy time."""

import types

import numpy as np
import pytest

from paddle_tpu.fluid import profiler

HLO_TEXT = """
%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {
  %fusion.7 = f32[8]{0} fusion(%gte), kind=kLoop, metadata={op_name="jit(train_step)/autodiff/jvp(layer_0_gated_delta_rule)/while/body/mul"}
  ROOT %tuple.9 = (s32[], f32[8]{0}) tuple(%i, %fusion.7)
}
ENTRY %main {
  %while.3 = (s32[], f32[8]{0}) while(%tuple.1), condition=%cond, body=%body, metadata={op_name="jit(train_step)/autodiff/jvp(layer_0_gated_delta_rule)/while"}
  %cond.4 = (f32[8]{0}) conditional(%p, %a, %b), true_computation=%t, false_computation=%f, metadata={op_name="jit(train_step)/autodiff/transpose(jvp(layer_0_moe_experts))/cond"}
  ROOT %fusion.8 = f32[8]{0} fusion(%gte.2), kind=kLoop, metadata={op_name="jit(train_step)/autodiff/transpose(jvp(layer_0_moe_experts))/mul"}
}
"""


@pytest.mark.parametrize("line,container", [
    ("%while.3 = (s32[], f32[2,3]{1,0}, /*index=5*/bf16[4]{0}) "
     "while(%tuple.1), condition=%c, body=%b", True),
    ("ROOT %cond.4 = (f32[8]{0}) conditional(%p, %a, %b)", True),
    ("%call.2 = f32[8]{0} call(%x), to_apply=%f", True),
    ("%fusion.12 = f32[2,3]{1,0} fusion(%p0), kind=kLoop, calls=%fused",
     False),
    ("%ragged-dot.5 = bf16[512,128]{1,0:T(8,128)(2,1)} custom-call(%a, %b), "
     "custom_call_target=\"tpu_custom_call\"", False),
    ("bench_window", False),
])
def test_container_instructions_are_told_from_work(line, container):
    assert profiler._is_container(line) is container


def test_region_table_leaves_the_container_out_so_rows_sum_to_busy():
    def event(name, start, dur):
        return types.SimpleNamespace(
            name=name, start_ns=start, duration_ns=dur,
            stats=[("device_offset_ps", start * 1000)])

    ops = [event("%while.3 = (s32[], f32[8]{0}) while(%tuple.1), "
                 "condition=%cond, body=%body", 0, 9000),   # spans the three
           event("%fusion.7 = f32[8]{0} fusion(%gte), kind=kLoop", 0, 3000),
           event("%fusion.7 = f32[8]{0} fusion(%gte), kind=kLoop", 3000,
                 3000),
           event("%fusion.7 = f32[8]{0} fusion(%gte), kind=kLoop", 6000,
                 3000),
           event("%cond.4 = (f32[8]{0}) conditional(%p, %a, %b)", 9000, 2000),
           event("%fusion.8 = f32[8]{0} fusion(%gte.2), kind=kLoop", 9000,
                 2000)]
    device = types.SimpleNamespace(name="/device:TPU:0", lines=[
        types.SimpleNamespace(name=profiler.DEVICE_MODULE_LINE, events=[
            event("jit_train_step(7)", 0, 12000)]),
        types.SimpleNamespace(name=profiler.DEVICE_OP_LINE, events=ops)])
    regions = profiler.device_time_by_region(
        types.SimpleNamespace(planes=[device]), {"jit_train_step": HLO_TEXT})
    assert regions["ops"] == {
        ("forward", "gated_delta_rule"): [3, pytest.approx(9e-6)],
        ("backward", "moe_experts"): [1, pytest.approx(2e-6)]}
    rows = sum(seconds for _, seconds in regions["phases"].values())
    assert rows == pytest.approx(regions["busy_s"]) == pytest.approx(11e-6)


def test_newest_step_regions_holds_no_container(monkeypatch):
    fn = types.SimpleNamespace(lower=lambda *specs: types.SimpleNamespace(
        compile=lambda: types.SimpleNamespace(
            as_text=lambda: HLO_TEXT, memory_analysis=lambda: None)))
    monkeypatch.setattr(profiler, "_NEWEST_STEP", None)
    monkeypatch.setattr(profiler, "_NEWEST_LOWERING", None)
    monkeypatch.setattr(profiler, "_NEWEST_REGIONS", None)
    assert profiler.newest_step_regions() is None       # no step yet
    profiler.note_compiled_step(fn, (np.zeros(2),))
    regions = profiler.newest_step_regions()
    assert regions == {"fusion.7": ("forward", "gated_delta_rule"),
                       "fusion.8": ("backward", "moe_experts")}
    assert profiler.newest_step_regions() is regions    # built once
    assert profiler.newest_step_memory() is None    # a backend without one
    # a step that cannot be lowered again (a disk-tier wrapper)
    profiler.note_compiled_step(object(), ())
    assert profiler.newest_step_regions() is None


# What the v5e's compiler makes of ``lax.ragged_dot`` inside ``moe_experts``
# (a compiled keye step's lines, shapes and payloads cut): its own kernel,
# whose ``op_name`` is the compiler's and names no program op.
RAGGED_TEXT = """
ENTRY %main {
  %select_multiply_fusion.12 = bf16[512,256]{1,0} fusion(%p.1, %p.2), kind=kLoop, metadata={op_name="jit(train_step)/layer_0_moe_experts/mul"}
  %convert_element_type.40 = bf16[4,256,128]{2,1,0} convert(%p.3), metadata={op_name="jit(train_step)/layer_0_cast/convert_element_type"}
  %ragged-dot-metadata.8 = (s32[5]{0}, s32[9]{0}, s32[9]{0}, s32[1]{0}) custom-call(%fusion.3), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-metadata"}
  %fusion.3 = s32[4]{0} fusion(%p.4), kind=kLoop, metadata={op_name="jit(train_step)/autodiff/transpose(jvp(layer_0_moe_experts))/sub"}
  %get-tuple-element.5 = s32[1]{0} get-tuple-element(%ragged-dot-metadata.8), index=3
  %ragged-dot-none.48 = f32[512,128]{1,0} custom-call(%get-tuple-element.5, %get-tuple-element.5, /*index=5*/%select_multiply_fusion.12, %convert_element_type.40), custom_call_target="tpu_custom_call", frontend_attributes={mosaic_fusion_entry_point="true"}, metadata={op_name="ragged-dot-none"}
  %attn.2 = bf16[8,128]{1,0} custom-call(%p.5), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/layer_0_fused_multihead_attention/attn_select_fwd"}
  %copy.9 = f32[8]{0} copy(%select_multiply_fusion.12)
}
"""


@pytest.mark.parametrize("instruction,region", [
    ("ragged-dot-none.48", ("forward", "moe_experts")),
    ("ragged-dot-metadata.8", ("backward", "moe_experts")),
    ("attn.2", ("forward", "fused_multihead_attention")),
    ("select_multiply_fusion.12", ("forward", "moe_experts")),
    ("copy.9", ("unattributed", "")),
])
def test_a_compilers_kernel_is_filed_under_what_feeds_it(instruction, region):
    """A ``custom-call`` whose ``op_name`` holds no scope takes its first
    scoped operand's; a kernel with a scope of its own, and an operation
    that XLA inserted without any ``op_name``, stay where they were."""
    names = profiler.op_names_of(RAGGED_TEXT)
    assert profiler.region_of(names.get(instruction, "")) == region
