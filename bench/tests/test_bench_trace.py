"""bench/trace.py: busy union, time by operation name and idle gaps named
by the harness span they fall in."""
import gzip
from pathlib import Path

import bench_tiny  # noqa: F401
import pytest

from bench import trace
from bench.trace import Event, Trace

DATA = Path(__file__).parent / "data"


def _synthetic():
    # window 0..10 s; device 0 runs two overlapping kernels and an
    # all-reduce; device 1 one long op; host spans label the gaps
    d0 = [Event(1.0, 3.0, "k_a"), Event(2.0, 4.0, "k_b"),
          Event(6.0, 7.0, "all-reduce.1"), Event(9.5, 11.0, "k_a")]
    d1 = [Event(-1.0, 5.0, "k_a")]
    spans = [Event(0.0, 10.0, "bench.window"),
             Event(0.0, 5.0, "bench.fit"), Event(5.0, 10.0, "bench.fit"),
             Event(4.2, 5.0, "bench.submit")]
    return Trace(devices={"/device:TPU:0": d0, "/device:TPU:1": d1},
                 spans=sorted(spans))


def test_busy_union_and_time_by_name():
    red = trace.reduce(_synthetic())
    assert red["window_s"] == 10.0
    d0 = red["devices"]["/device:TPU:0"]
    assert d0["busy_s"] == pytest.approx(3.0 + 1.0 + 0.5)   # 1-4, 6-7, 9.5-10
    assert d0["ops"]["k_a"] == pytest.approx(2.0 + 0.5)
    assert red["devices"]["/device:TPU:1"]["busy_s"] == pytest.approx(5.0)
    assert red["busy_s"] == pytest.approx((4.5 + 5.0) / 2)
    assert trace.op_time(red, lambda n: "all-reduce" in n) == {
        "/device:TPU:0": 1.0, "/device:TPU:1": 0.0}
    names = [n for n, _ in red["device_ops"]]
    assert names[0] == "k_a"


def test_idle_gaps_are_named_by_the_innermost_span():
    red = trace.reduce(_synthetic())
    # device 0 idles 0-1, 4-6, 7-9.5: longest first; 4-6 is centred at 5.0,
    # which only the second bench.fit covers
    assert red["idle_gaps"] == [["bench.fit", pytest.approx(2.5)],
                                ["bench.fit", pytest.approx(2.0)],
                                ["bench.fit", pytest.approx(1.0)]]
    shifted = Trace(devices=_synthetic().devices,
                    spans=sorted(_synthetic().spans
                                 + [Event(4.5, 5.5, "bench.submit")]))
    assert trace.reduce(shifted)["idle_gaps"][1][0] == "bench.submit"


def test_needs_one_window_and_a_device():
    t = _synthetic()
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce(Trace(devices=t.devices, spans=[]))
    with pytest.raises(ValueError, match="no device operation"):
        trace.reduce(Trace(devices={}, spans=t.spans))


def _recorded():
    """One fit (4 f/g, 3 Hd; n=32,768, m=1,024, d=54, otf_shard, Pallas)
    traced on a TPU v5 lite by the fit driver, the profiler as
    ``bench/run.py`` sets it."""
    return gzip.open(DATA / "fit_small.xplane.pb.gz").read()


def test_recorded_trace_planes_and_spans():
    t = trace.read(_recorded())
    assert list(t.devices) == ["/device:TPU:0"]
    assert len(t.devices["/device:TPU:0"]) == 152
    assert [s.name for s in t.spans] == ["bench.window", "bench.fit"]


def test_recorded_trace_kernel_time_and_busy_union():
    from jax.profiler import ProfileData
    data = _recorded()
    red = trace.reduce(trace.read(data))
    # straight from the profiler's own reader: every kmvp call of the fit
    # (7 evaluations: two forward passes, one transposed) and its duration
    plane = ProfileData.from_serialized_xspace(data).find_plane_with_name(
        "/device:TPU:0")
    ops = [e for line in plane.lines if line.name == "XLA Ops"
           for e in line.events]
    fwd = [e.duration_ns for e in ops if e.name.startswith("%kmvp_fwd.")]
    tt = [e.duration_ns for e in ops if e.name.startswith("%kmvp_t.")]
    assert (len(fwd), len(tt)) == (14, 7)
    ops_time = red["devices"]["/device:TPU:0"]["ops"]
    assert ops_time["kmvp_fwd"] == pytest.approx(sum(fwd) * 1e-9)
    assert ops_time["kmvp_t"] == pytest.approx(sum(tt) * 1e-9)
    # self times add up to the busy union (nested ops are not counted
    # twice), which is below the window
    assert sum(ops_time.values()) == pytest.approx(red["busy_s"], rel=1e-6)
    assert red["busy_s"] < red["window_s"]
    assert red["window_s"] == pytest.approx(0.26940215)


def test_recorded_trace_idle_gaps():
    red = trace.reduce(trace.read(_recorded()))
    gaps = red["idle_gaps"]
    # the fit's host work before its first device op: tracing and a
    # persistent-cache load of the solve, inside the one bench.fit span
    assert gaps[0][0] == "bench.fit" and gaps[0][1] > 0.2
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps),
                                          reverse=True)
    assert sum(g[1] for g in gaps) <= red["window_s"] - red["busy_s"] + 1e-9
