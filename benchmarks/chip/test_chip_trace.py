"""The reduction from a profiler trace to the device numbers, and the
scoring kernel's work count, on hand-built inputs (no chip, no
libtpu)."""

import importlib.util
import os

import pytest

import work
import xtrace
from xtrace import Event, Line, Plane


def _trace():
    """A 100 ns window: a loop [10, 40) holding fusion.1 [10, 25) and the
    kernel [25, 40), fusion.1 again [60, 70), and a copy spilling past
    the window's end; modules and host spans beside."""
    device = Plane("/device:TPU:0", (
        Line.of(xtrace.OPS_LINE, (
            Event("while.3", 10, 30), Event("fusion.1", 10, 15),
            Event("mapscore_kernel", 25, 15), Event("fusion.1", 60, 10),
            Event("copy", 95, 20))),
        Line.of(xtrace.MODULES_LINE, (
            Event("jit_run(abc)", 10, 30), Event("jit__engine", 60, 10),
            Event("jit_run(abc)", 95, 20))),
    ))
    host = Plane("/host:CPU", (
        Line.of("python", (Event(xtrace.WINDOW, 0, 100),
                           Event("other", 0, 5))),))
    custom = Plane("/device:CUSTOM:Megascale Trace", ())
    return [host, device, custom]


def test_window_and_device_planes():
    planes = _trace()
    assert xtrace.window(planes) == (0, 100)
    assert [p.name for p in xtrace.device_planes(planes)] == [
        "/device:TPU:0"]
    with pytest.raises(LookupError):
        xtrace.window(planes, "missing")


def test_busy_union_and_idle_share():
    dev = _trace()[1]
    # [10, 40) merged, [60, 70), [95, 100) clipped: 30 + 10 + 5
    assert xtrace.union([10, 25, 60, 95], [30, 40, 70, 115], 0,
                        100) == [(10, 40), (60, 70), (95, 100)]
    assert xtrace.union([], [], 0, 100) == []
    assert xtrace.busy_ns(dev, 0, 100) == 45
    assert xtrace.gaps(dev, 0, 100) == [(0, 10), (40, 60), (70, 95)]
    assert 1 - xtrace.busy_ns(dev, 0, 100) / 100 == pytest.approx(0.55)


def test_module_and_op_time():
    dev = _trace()[1]
    assert xtrace.module_ns(dev, "jit_run", 0, 100) == 30 + 5
    assert xtrace.module_ns(dev, "jit__engine", 0, 100) == 10
    assert xtrace.op_ns(dev, lambda n: "mapscore" in n, 0, 100) == (15, 1)
    # leaves only: the loop's 30 ns are its body's
    assert xtrace.leaves(dev.line(xtrace.OPS_LINE)).tolist() == [
        False, True, True, True, True]
    top = xtrace.top_ops(dev, 0, 100)
    assert top == [["fusion.1", 25e-9], ["mapscore_kernel", 15e-9],
                   ["copy", 5e-9]]


def test_gap_attribution_by_innermost_span():
    spans = [("serve.request", 0, 80), ("pipeline.map", 35, 75),
             ("bench.client", 85, 100)]
    got = xtrace.attribute([(0, 10), (40, 60), (70, 95)], spans)
    # midpoints 5 -> request, 50 -> pipeline.map (inner), 82.5 -> none
    assert got == pytest.approx({"serve.request": 10e-9,
                                 "pipeline.map": 20e-9,
                                 "outside any span": 25e-9})


def test_mapscore_work_hand_counted():
    # 2 candidates, 10 messages, 3 router dims (two wrap), 4 cores
    w = work.mapscore_work(ncand=2, messages=10, router_dims=(4, 2, 2),
                           wrap=(True, True, False), cores_per_node=4,
                           traffic=False)
    # bytes: weights 10*4, endpoints 2 cand * 2 ends * 10 * 3 cols * 4,
    # results 2 * 4 * 4
    assert w.bytes == 40 + 480 + 32
    # per message and candidate: hops 5 + 5 + 3, weighted sum 2
    assert w.flops == 2 * 10 * 15
    t = work.mapscore_work(ncand=2, messages=10, router_dims=(4, 2, 2),
                           wrap=(True, True, False), cores_per_node=4,
                           traffic=True)
    # 4 columns when routing, and 8 bytes of inverse bandwidths a dim
    assert t.bytes == 40 + 2 * 2 * 10 * 4 * 4 + 32 + 4 * 8
    # routing: 8 per message, dim and candidate; 4 per link per
    # candidate over 2 directions * 3 dims * 16 routers * 4 cores
    assert t.flops == 300 + 2 * (10 * 3 * 8 + 2 * 3 * 16 * 4 * 4)


def test_least_time_and_peaks():
    peak = work.peaks("TPU v5 lite")
    assert peak["hbm_bytes_per_s"] == 819e9
    secs, bound = work.least_seconds(work.Work(1.0, 819e9), peak)
    assert (secs, bound) == (1.0, "bytes")
    secs, bound = work.least_seconds(work.Work(197e12 * 2, 1.0), peak)
    assert (secs, bound) == (2.0, "operations")
    with pytest.raises(KeyError):
        work.peaks("an unknown chip")


def _roofline_reader():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "layers", "mapscore_roofline.py")
    spec = importlib.util.spec_from_file_location("roofline_reader", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_kernel_event_found_by_its_result_tiles():
    """The unnamed kernel is the custom call with the kernel's two
    (candidates, 8, 128) results; nothing else on a v5e trace is."""
    is_kernel = _roofline_reader().is_kernel
    assert is_kernel(
        "%_unknown_.1 = (f32[4,8,128]{2,1,0:T(8,128)S(1)}, "
        "s32[4,8,128]{2,1,0:T(8,128)}) custom-call(s32[4,6,262144]"
        "{2,1,0:T(8,128)S(1)} %bitcast.565, s32[4,6,262144]")
    assert is_kernel("%mapscore.2 = f32[1] fusion()")
    assert not is_kernel(
        "%fusion.756 = f32[655360]{0:T(1024)} fusion(f32[131072,5]"
        "{1,0:T(8,128)S(1)} %copy.759, s32[655360]{0:T(1024)S(1)} "
        "%custom-call.305), kind=kCustom")
    assert not is_kernel(
        "%custom-call.305 = s32[655360]{0:T(1024)} custom-call(s32[4])")


def test_trace_that_stops_early_is_refused():
    planes = _trace()
    # ops run until 115 ns (one spills past the window's end at 100)
    xtrace.check_complete(planes, (0, 100), request_s=50e-9)
    # a device whose last op ends at 70 in a window to 200 dropped events
    with pytest.raises(RuntimeError, match="dropped events"):
        xtrace.check_complete(planes, (0, 200), request_s=50e-9)
