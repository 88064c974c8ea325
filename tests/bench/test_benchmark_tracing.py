"""The reduction from a profiler trace to numbers, on a small recorded
trace (``data/small.xplane.textproto``, read through the profiler's own
parser) and on hand-made intervals."""

import os

import pytest

from benchmarks import tracing

DATA = os.path.join(os.path.dirname(__file__), "data")


@pytest.fixture(scope="module")
def trace():
    from jax.profiler import ProfileData

    with open(os.path.join(DATA, "small.xplane.textproto")) as fh:
        return tracing.from_profile(ProfileData.from_text_proto(fh.read()))


def test_window_is_the_traced_span(trace):
    assert trace.window_ns == (1000, 101000)
    assert tracing.window_seconds(trace) == pytest.approx(100e-6)


def test_busy_seconds_per_device(trace):
    busy = tracing.busy_seconds(trace)
    assert busy[0] == pytest.approx(70e-6)
    assert busy[1] == pytest.approx(50e-6)


def test_idle_share_is_mean_over_devices(trace):
    assert tracing.idle_share(trace) == pytest.approx(1 - 0.6)
    s = tracing.summary(trace)
    assert s["busy_s"] == pytest.approx(60e-6)
    assert s["window_s"] == pytest.approx(100e-6)


@pytest.mark.parametrize("match,expected", [
    ("jit_step", [50e-6, 50e-6]), ("jit_other", [20e-6]),
    ("jit_", [50e-6, 20e-6, 50e-6]), ("absent", []),
])
def test_program_seconds_by_name(trace, match, expected):
    assert sorted(tracing.program_seconds(trace, match)) == pytest.approx(sorted(expected))


def test_exposed_collective_time(trace):
    exposed = tracing.exposed_collective_seconds(trace)
    assert exposed[0] == pytest.approx(20e-6)  # nothing else ran under it
    assert exposed[1] == pytest.approx(10e-6)  # 10 of its 20 us under a fusion


def test_breakdown_groups_ops_and_names_gaps(trace):
    b = tracing.breakdown(trace)
    ops = dict(map(tuple, b["device_ops"]))
    assert ops["fusion"] == pytest.approx(80e-6)
    assert ops["all-reduce"] == pytest.approx(40e-6)
    assert ops["copy"] == pytest.approx(10e-6)
    gaps = dict(map(tuple, b["idle_gaps"]))
    # device 0 idles 50-60 us (under server_step) and 80-100 us (under nothing)
    assert gaps == {"server_step": pytest.approx(10e-6), "unannotated": pytest.approx(20e-6)}
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_foreign_host_events_are_not_spans(trace):
    assert sorted(n for n, _, _ in trace.host) == ["server_step", "traced_window"]


def test_window_falls_back_to_device_events():
    t = tracing.Trace(ops={0: [("a.1", 10, 20), ("b", 40, 50)]}, modules={}, host=[])
    assert t.window_ns == (10, 50)
    assert tracing.idle_share(t) == pytest.approx(0.5)


def test_no_device_operation_is_an_error():
    t = tracing.Trace(ops={0: []}, modules={}, host=[("traced_window", 0, 100)])
    with pytest.raises(ValueError):
        tracing.summary(t)


@pytest.mark.parametrize("a,b,expected", [
    ([(0, 10)], [], [(0, 10)]),
    ([(0, 10)], [(2, 4), (6, 8)], [(0, 2), (4, 6), (8, 10)]),
    ([(0, 10), (20, 30)], [(5, 25)], [(0, 5), (25, 30)]),
    ([(0, 10)], [(0, 10)], []),
])
def test_subtract(a, b, expected):
    assert tracing.subtract(a, b) == expected


def test_merge_overlapping():
    assert tracing.merge([(5, 8), (0, 3), (2, 6), (10, 11)]) == [(0, 8), (10, 11)]


@pytest.mark.parametrize("name,group", [
    ("fusion.123", "fusion"), ("%copy.4", "copy"), ("all-reduce-start.1", "all-reduce-start"),
    ("convolution_add_fusion", "convolution_add_fusion"),
])
def test_op_group(name, group):
    assert tracing.op_group(name) == group
