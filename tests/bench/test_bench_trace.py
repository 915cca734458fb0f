"""The trace reducer on a small trace: two device ops lines, host
annotations, busy union, idle share and gaps, kernel time by name, and
idle gaps labelled by the host span that covers them."""
import pytest

from bench import trace

US = 1000.0   # ns


def small_trace():
    # events are named by the HLO instruction's text; a loop's event spans
    # its body's events
    kernel = "%vmap_jit_quant_dequant__.2 = f32[8,12544,32]{2,1,0} custom-call(%p)"
    ops = [("%while.7 = (s32[], f32[8,32]) while(%t)", 0 * US, 60 * US),
           ("%fusion.1 = f32[8,32]{1,0} fusion(%a)", 0 * US, 10 * US),
           ("%fusion.2 = f32[8,32]{1,0} fusion(%b)", 5 * US, 10 * US),
           (kernel, 20 * US, 5 * US),
           ("%convolution.3 = f32[16,112,112,32]{3,2,1,0} convolution("
            "%vmap_jit_quant_dequant__.2)", 40 * US, 20 * US),
           (kernel, 70 * US, 5 * US)]
    return trace.from_planes([
        ("/device:TPU:0", [("XLA Ops", ops), ("XLA Modules", [
            ("jit_round", 0, 80 * US)])]),
        ("/host:CPU", [("python", [("bench/round", 0, 35 * US),
                                   ("bench/round", 35 * US, 45 * US),
                                   ("other", 0, 80 * US)])]),
        ("/host:metadata", []),
    ])


def test_planes_and_marks():
    tr = small_trace()
    assert tr.devices == [0]
    assert len(tr.ops[0]) == 6          # the modules line is not counted
    assert [m.name for m in tr.marks] == ["bench/round", "bench/round"]
    assert trace.window(tr) == (0, 80 * US)


def test_busy_union_and_idle():
    ops = small_trace().ops[0]
    leaf = ops[1:]
    assert trace.busy_ns(leaf, 0, 80 * US) == pytest.approx(45 * US)
    assert trace.gaps(leaf, 0, 80 * US) == [(15 * US, 20 * US),
                                            (25 * US, 40 * US),
                                            (60 * US, 70 * US),
                                            (75 * US, 80 * US)]
    # clipped to a window
    assert trace.busy_ns(leaf, 8 * US, 22 * US) == pytest.approx(9 * US)
    # the loop covers its body: the union counts it once
    assert trace.busy_ns(ops, 0, 80 * US) == pytest.approx(65 * US)


def test_kernel_time_and_top_ops():
    ops = small_trace().ops[0]
    # matched on the instruction's own name, not on its operands'
    assert trace.time_by_name(ops, r"quant_dequant") == (2, 10 * US)
    top = trace.top_ops(ops, 2)
    assert top[0][0] == "convolution.3 f32[16,112,112,32]"
    assert top[0][1] == pytest.approx(20e-6)
    assert top[1][0].startswith("fusion.1") or top[1][0].startswith(
        "vmap_jit_quant_dequant__.2")
    assert len(top) == 2


def test_gaps_labelled_by_host_span():
    ops = small_trace().ops[0][1:]
    spans = [("round/sample", 24 * US, 41 * US),
             ("round/eval", 59 * US, 72 * US),
             ("round/account", 60 * US, 71 * US)]
    got = dict(trace.label_gaps(trace.gaps(ops, 0, 80 * US), spans))
    assert got["round/sample"] == pytest.approx(15e-6)
    assert got["round/account"] == pytest.approx(10e-6)   # innermost
    assert got["between rounds"] == pytest.approx(10e-6)
