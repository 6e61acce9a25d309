"""The reduction from a profiler trace to device times, on a small
synthetic trace: busy union, own time of nested ops, scope attribution
through the HLO text, kernels and idle gaps."""
import pytest

from bench import trace as T

HLO = """
%region_body (arg: (s32[], f32[8])) -> (s32[], f32[8]) {
  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fc, metadata={op_name="jit(chunk)/while/body/round.probe/conv_general_dilated"}
  %custom-call.2 = (s32[20]{0}, s32[20]{0}) custom-call(%a, %b), custom_call_target="tpu_custom_call", metadata={op_name="jit(chunk)/while/body/round.selection/pallas_call"}
  %custom-call.3 = f32[2048]{0} custom-call(%c), custom_call_target="tpu_custom_call", metadata={op_name="jit(chunk)/while/body/round.aggregation/pallas_call"}
  %all-gather.4 = f32[40]{0} all-gather(%d), dimensions={0}
  ROOT %while.5 = (s32[], f32[8]{0}) while(%t), condition=%c, body=%b, metadata={op_name="jit(chunk)/while/body/round.local_update/while"}
  %fusion.6 = f32[8]{0} fusion(%q), kind=kLoop, metadata={op_name="jit(chunk)/while/body/round.local_update/mul"}
}
"""


def _trace():
    hlo = T.parse_hlo(HLO)
    ops = [
        T.Op(0, "jit_chunk", "fusion.1", 100, 100),
        T.Op(0, "jit_chunk", "custom-call.2", 250, 10),
        T.Op(0, "jit_chunk", "while.5", 300, 200),       # holds fusion.6
        T.Op(0, "jit_chunk", "fusion.6", 320, 50),
        T.Op(0, "jit_chunk", "custom-call.3", 600, 40),
        T.Op(0, "jit_chunk", "all-gather.4", 650, 30),
        T.Op(0, "jit_evaluate", "fusion.1", 900, 50),    # another module
        T.Op(0, "jit_chunk", "fusion.1", 2000, 100),     # after the window
    ]
    spans = [T.Span(T.WINDOW_SPAN, 0, 1000), T.Span("chunk", 0, 1000),
             T.Span("eval", 690, 200), T.Span("dispatch", 10, 20)]
    tr = T.Trace(ops, spans, (0, 1000), [0])
    T.attach(tr.ops, hlo)
    return tr


def test_parse_hlo_reads_opcode_scope_and_kernels():
    hlo = T.parse_hlo(HLO)
    assert hlo["fusion.1"] == T.OpInfo("fusion", "round.probe", False)
    assert hlo["custom-call.2"] == T.OpInfo("custom-call", "round.selection",
                                            True)
    assert hlo["while.5"].opcode == "while"
    assert hlo["all-gather.4"] == T.OpInfo("all-gather", "", False)


def test_op_name_from_event_text():
    assert T.op_name("%fusion.427 = f32[16]{0} fusion(bf16[8])") \
        == "fusion.427"


def test_busy_union_and_idle():
    tr = _trace()
    assert T.busy_intervals(tr, 0) == [(100, 200), (250, 260), (300, 500),
                                       (600, 640), (650, 680), (900, 950)]
    assert T.busy_s(tr) == pytest.approx(430e-9)
    assert tr.window_s == pytest.approx(1000e-9)


def test_own_time_and_scopes():
    tr = _trace()
    by = {o.name: o.self_ns for o in tr.ops if o.module == "jit_chunk"}
    assert by["while.5"] == 150          # 200 less its nested fusion.6
    assert T.scope_s(tr, "round.local_update") == pytest.approx(200e-9)
    assert T.scope_s(tr, "round.probe") == pytest.approx(100e-9)
    assert T.kernel_s(tr, "round.selection") == pytest.approx(10e-9)
    assert T.kernel_s(tr, "round.aggregation") == pytest.approx(40e-9)
    assert T.kernel_s(tr, "round.dynamics") is None


def test_top_ops_and_idle_gaps_by_host_span():
    tr = _trace()
    top = T.top_ops(tr, 3)
    assert top[0] == ["round.local_update/while.5", pytest.approx(150e-9)]
    assert [t[0] for t in top[1:]] == ["round.probe/fusion.1",
                                       "round.local_update/fusion.6"]
    gaps = T.idle_gaps(tr, 3)
    # the longest gaps: [680,900] under "eval", then [0,100] (its middle
    # lies outside "dispatch") and [500,600], both under "chunk" alone
    assert gaps[0] == ["eval", pytest.approx(220e-9)]
    assert gaps[1] == ["chunk", pytest.approx(100e-9)]
    assert len(gaps) == 3


def test_ops_of_two_chips_average():
    tr = _trace()
    tr.ops.append(T.Op(1, "jit_chunk", "fusion.1", 100, 300))
    tr.chips.append(1)
    T.attach(tr.ops, T.parse_hlo(HLO))
    assert T.scope_s(tr, "round.probe") == pytest.approx(200e-9)
    assert T.busy_s(tr) == pytest.approx((430e-9 + 300e-9) / 2)


def test_staged_kernel_time_follows_the_copies_into_the_kernel():
    hlo = T.parse_hlo("""
  %pad.1 = f32[24,64]{1,0:T(8,128)S(1)} pad(%get-tuple-element.9, %constant.2), padding=0_4x0_0
  %copy.2 = f32[24,64]{1,0:T(8,128)S(1)} copy(%pad.1)
  %bitcast.3 = f32[20,64]{1,0:T(8,128)S(1)} bitcast(%copy.2)
  %fusion.4 = f32[20,1]{1,0} fusion(%w), kind=kLoop, metadata={op_name="jit(chunk)/round.aggregation/div"}
  %custom-call.5 = f32[64]{0} custom-call(%fusion.4, %bitcast.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(chunk)/round.aggregation/pallas_call"}
  %copy.6 = f32[64]{0} copy(%get-tuple-element.9)
""")
    assert hlo["custom-call.5"].operands == ("fusion.4", "bitcast.3")
    assert hlo["pad.1"].operands == ("get-tuple-element.9", "constant.2")
    ops = [T.Op(0, "jit_chunk", "pad.1", 0, 30),
           T.Op(0, "jit_chunk", "copy.2", 40, 20),
           T.Op(0, "jit_chunk", "fusion.4", 70, 5),
           T.Op(0, "jit_chunk", "custom-call.5", 80, 10),
           T.Op(0, "jit_chunk", "copy.6", 100, 50)]   # feeds no kernel
    tr = T.Trace(ops, [T.Span(T.WINDOW_SPAN, 0, 200)], (0, 200), [0])
    T.attach(tr.ops, hlo)
    # the kernel and the pad and copy that stage its stack; not the
    # weights' fusion, and not a copy that feeds no kernel
    assert T.staged_kernel_s(tr, "round.aggregation", hlo) \
        == pytest.approx(60e-9)
    assert T.kernel_s(tr, "round.aggregation") == pytest.approx(10e-9)
    assert T.staged_kernel_s(tr, "round.selection", hlo) is None
