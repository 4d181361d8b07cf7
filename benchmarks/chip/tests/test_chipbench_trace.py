"""The trace reduction: on hand-made events, and on a small trace
recorded on a v5e (``record_trace.py``)."""
import os

import chipbench_testkit  # noqa: F401  (puts the harness on sys.path)
import pytest

from chipbench import tracefile

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                       "tiny_aida.xplane.pb.gz")


def test_union_merges_overlaps_and_keeps_gaps():
    assert tracefile.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == \
        [(0, 3), (5, 8)]


def test_self_time_takes_nested_ops_out_of_their_loop():
    events = [(0, 100, "while.1 (s32[])"), (10, 30, "fusion.2 f32[8]"),
              (40, 90, "_spmv_call.3 f32[8,32,128]"),
              (120, 130, "fusion.2 f32[8]")]
    got = tracefile.self_times(events)
    assert got == {"while.1 (s32[])": 30, "fusion.2 f32[8]": 30,
                   "_spmv_call.3 f32[8,32,128]": 50}


def test_idle_gaps_are_laid_at_the_innermost_host_span():
    window = (0, 1000, tracefile.WINDOW_SPAN)
    host = [window, (0, 400, "bench.step"), (300, 400, "np.asarray"),
            (400, 1000, "bench.wait")]
    mods = [(0, 250), (250, 300), (500, 600)]
    ops = [(0, 250, "%fusion.1 = f32[4]{0} fusion()"),
           (250, 300, "%_spmv_call.7 = f32[8,32,128]{2,1,0} custom-call()"),
           (500, 600, "%copy.2 = bf16[2]{0} copy()")]
    red = tracefile.reduce_events([(mods, ops)], [host])
    assert red.window_s == pytest.approx(1000e-9)
    assert red.busy_s == pytest.approx(400e-9)          # 0-300, 500-600
    assert red.gaps_s == {"np.asarray": pytest.approx(100e-9),
                          "bench.wait": pytest.approx(500e-9)}
    assert red.ops_matching(r"^_spmv_call") == pytest.approx(50e-9)
    assert red.op_s["fusion.1 f32[4]"] == pytest.approx(250e-9)
    bd = red.breakdown()
    assert bd["device_ops"][0][0] == "fusion.1 f32[4]"
    assert bd["idle_gaps"][0] == ["bench.wait", pytest.approx(500e-9)]


def test_no_window_span_is_an_error():
    with pytest.raises(ValueError, match="bench.window"):
        tracefile.reduce_events([([], [])], [[(0, 5, "bench.step")]])


@pytest.fixture(scope="module")
def recorded():
    if not os.path.exists(FIXTURE):
        pytest.fail(f"missing fixture {FIXTURE}; record it on a TPU with "
                    "record_trace.py")
    return tracefile.reduce(FIXTURE)


def test_recorded_trace_busy_is_the_union_of_its_programs(recorded):
    import gzip
    from jax.profiler import ProfileData
    with gzip.open(FIXTURE) as f:
        data = ProfileData.from_serialized_xspace(f.read())
    host = [e for p in data.planes if p.name.startswith("/host:")
            for ln in p.lines for e in ln.events
            if e.name == tracefile.WINDOW_SPAN]
    w0 = host[0].start_ns
    w1 = w0 + host[0].duration_ns
    dev = [p for p in data.planes if tracefile.DEVICE_PLANE.match(p.name)]
    assert len(dev) == recorded.chips == 1
    mods = [(max(e.start_ns, w0), min(e.start_ns + e.duration_ns, w1))
            for ln in dev[0].lines if ln.name == "XLA Modules"
            for e in ln.events
            if e.start_ns + e.duration_ns > w0 and e.start_ns < w1]
    busy = sum(b - a for a, b in tracefile.union(mods)) / 1e9
    assert recorded.busy_s == pytest.approx(busy)
    assert 0 < recorded.busy_s < recorded.window_s
    assert recorded.window_s == pytest.approx((w1 - w0) / 1e9)
    # busy and idle fill the window, and every gap has a host label
    assert recorded.busy_s + sum(recorded.gaps_s.values()) == \
        pytest.approx(recorded.window_s)
    assert all(label for label in recorded.gaps_s)


def test_recorded_trace_has_the_aida_kernel(recorded):
    spmv = recorded.ops_matching(r"^_spmv_call")
    assert 0 < spmv <= recorded.busy_s
    # a roofline share is the least time over the kernel's device time:
    # a least time no longer than the kernel's gives a share <= 100%
    least = 0.5 * spmv
    assert 0 < 100.0 * least / spmv <= 100.0
