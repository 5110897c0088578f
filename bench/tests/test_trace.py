"""The reduction from a profiler trace to the per-layer readings, on a
short trace of ``lbs_range.hotspot.rate`` recorded on a TPU v5 lite and
kept in ``bench/tests/data``."""
import os

import pytest

from tracing import (COLLECTIVE, WINDOW_MODULE, Readings, Trace, gaps_ns,
                     union_ns)

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "range_small.xplane.pb")


def test_union_and_gaps():
    iv = [(0, 10), (5, 15), (20, 30), (40, 45)]
    assert union_ns(iv, 0, 50) == 15 + 10 + 5
    assert union_ns(iv, 8, 42) == 7 + 10 + 2
    assert gaps_ns(iv, 0, 50) == [(15, 20), (30, 40), (45, 50)]
    assert gaps_ns([], 0, 5) == [(0, 5)]


@pytest.fixture(scope="module")
def trace():
    return Trace(DATA)


def test_recorded_trace_has_device_ops_and_annotations(trace):
    assert trace.ops and all(trace.ops.values())
    dev = sorted(trace.ops)[0]
    assert dev.startswith("/device:TPU")
    assert any(WINDOW_MODULE.search(n) for n, _, _ in trace.modules[dev])
    assert not any(COLLECTIVE.search(n) for n, _, _ in trace.ops[dev])
    assert any(a[0] == "bench_call" for a in trace.annotations)


def test_readings_of_the_recorded_trace(trace):
    calls = [(s, e) for n, s, e in trace.annotations if n == "bench_call"]
    conf = {"deployment": {"grid_size": 64}}
    r = Readings(trace, [], calls, conf, {"hbm_bytes_per_s": 819e9}, [])
    assert r.offset == 0
    assert 0 < r.busy_s() < r.window_s()
    assert r.op_ns(WINDOW_MODULE, modules=True) > 0
    b = r.breakdown()
    assert 0 < len(b["device_ops"]) <= 10 and 0 < len(b["idle_gaps"]) <= 10
    assert b["idle_gaps"][0][0] in ("bench_call", "bench_wait", "outside")
    longest = b["idle_gaps"][0][1]
    assert all(g[1] <= longest for g in b["idle_gaps"])


def test_unspanned_time_per_round(trace):
    import run as R
    calls = [(s, e) for n, s, e in trace.annotations if n == "bench_call"]
    conf = {"deployment": {"grid_size": 64}}
    # two rounds inside the window; one span covers the first call whole,
    # and a second covers half of the second call
    (a0, a1), (b0, b1) = calls[0], calls[1]
    spans = [("fused_window", a0, a1 - a0, {}, 1, None),
             ("round_close", b0, (b1 - b0) // 2, {}, 2, None)]
    rounds = [(a0, 8, 10), (b0, 16, 10)]
    r = Readings(trace, spans, calls, conf, {"hbm_bytes_per_s": 819e9},
                 rounds)
    total = sum(e - s for s, e in calls)
    want = (total - (a1 - a0) - (b1 - b0) // 2) / 2 / 1e6
    got = R.reader("unspanned_ms.lat")(r)
    assert abs(got - want) < 1e-6
    assert R.reader("unspanned_ms.lat")(
        Readings(trace, spans, calls, conf, {}, [])) is None
