"""The trace reduction (busy and idle time, per-program time) and the
breakdown's gap labels, on small traces whose answers are known."""

import os

import pytest

from benchmark import breakdown, trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_union_and_gaps():
    ivs = [(5, 8), (0, 2), (1, 3), (7, 9), (20, 30)]
    busy = trace.union(ivs, 0, 25)
    assert busy == [(0, 3), (5, 9), (20, 25)]
    assert trace.union([(0, 1)], 2, 3) == []
    assert breakdown.gaps(busy, -1, 26) == [(-1, 0), (3, 5), (9, 20), (25, 26)]


def test_label_gap_takes_the_largest_overlap():
    host = {"host_marshal": [(0, 6)], "pipeline_linger": [(6, 7)]}
    assert breakdown.label_gap((2, 10), host, "between") == "host_marshal"
    assert breakdown.label_gap((20, 30), host, "between") == "between"


class _Ev:
    def __init__(self, name, start, dur):
        self.name, self.start_ns, self.duration_ns = name, start, dur


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class _Profile:
    def __init__(self, planes):
        self.planes = planes


def _profile():
    """A window of 1,000 ns; two executions of one program, 300 ns of ops
    (one op overlapping another) on the chip, and one op outside the window."""
    host = _Plane("/host:CPU", [_Line("python", [_Ev("bench_window", 1000, 1000)])])
    dev = _Plane("/device:TPU:0", [
        _Line("XLA Modules", [_Ev("jit__device_verify(1)", 1100, 200),
                              _Ev("jit__device_verify(1)", 1500, 150),
                              _Ev("jit_other", 2500, 10)]),
        _Line("XLA Ops", [_Ev("fusion.1", 1100, 150), _Ev("fusion.2", 1200, 100),
                          _Ev("fusion.1", 1500, 100), _Ev("fusion.3", 2500, 10)]),
    ])
    return _Profile([host, dev])


def test_device_trace_reduction():
    t = trace.DeviceTrace.from_profile(_profile(), "bench_window")
    assert t.window_s == pytest.approx(1e-6)
    assert t.busy_s == pytest.approx(300e-9)   # [1100, 1300) and [1500, 1600)
    assert t.module_seconds("_device_verify") == pytest.approx([200e-9, 150e-9])


def test_breakdown_names_the_gaps():
    """The host's perf counter read 10.0 s when the window opened."""
    t = trace.DeviceTrace.from_profile(_profile(), "bench_window")
    spans = {"device_batch_setup": [{"start_pc": 10.0 + 3e-7, "end_pc": 10.0 + 5e-7}]}
    out = breakdown.breakdown(t, spans, [(10.0, 10.0 + 7e-7)], 10.0)
    assert out["device_ops"][0] == ["fusion.1", pytest.approx(250e-9)]
    assert out["idle_gaps"][0] == ["between_requests", pytest.approx(400e-9)]
    assert ["host_marshal", pytest.approx(200e-9)] in out["idle_gaps"]


def test_missing_window_is_an_error():
    with pytest.raises(RuntimeError):
        trace.DeviceTrace.from_profile(_profile(), "no_such_window")


def test_recorded_chip_trace():
    """A trace recorded on a TPU v5e (my chip run, PR 22): a small jitted
    program run three times inside the host annotation.  The device clock of
    this trace runs ~1 ms behind the host's, so the first execution (35 us,
    dispatched right after the annotation opened) lies before the window and
    two are read; a cell's traced requests begin with seconds of host work,
    so no execution of theirs lies that close to the edge."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(os.path.join(DATA, "tpu_probe.xplane.pb"))
    t = trace.DeviceTrace.from_profile(pd, "bench_window")
    assert t.window_s == pytest.approx(0.03263715)
    runs = t.module_seconds("jit_f")
    assert runs == pytest.approx([35489e-9, 35490e-9])
    assert t.busy_s == pytest.approx(sum(runs), rel=0.01)
    ops = breakdown.breakdown(t, {}, [], 0.0)["device_ops"]
    assert "fusion" in {name for name, _ in ops}
