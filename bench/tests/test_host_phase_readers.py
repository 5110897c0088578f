"""The readers of the program's re-index and window host-phase spans, on
hand-built spans laid over the ``bench_call`` annotations of the short
``lbs_range.hotspot.rate`` trace kept in ``bench/tests/data``."""
import os

import pytest

import run as R
from tracing import Readings, Trace

DATA = os.path.join(os.path.dirname(__file__), "data",
                    "range_small.xplane.pb")


@pytest.fixture(scope="module")
def trace():
    return Trace(DATA)


def test_host_phase_readers_on_hand_built_spans(trace):
    calls = [(s, e) for n, s, e in trace.annotations if n == "bench_call"]
    conf = {"deployment": {"grid_size": 64}}
    a0, b0 = calls[0][0], calls[1][0]
    ms = 1_000_000
    window = [("fused_window", a0, 8 * ms, {"ticks": 8}, 1, -1),
              ("window_bin", a0 + ms, 2 * ms, {}, 2, 1),
              ("window_upload", a0 + 3 * ms, ms // 2, {}, 3, 1),
              ("window_readback", a0 + 5 * ms, ms // 4, {}, 4, 1)]
    round_ = [("router_round", b0, 10 * ms, {}, 5, -1)]
    reindex = [("reindex_queries", b0 + ms, 6 * ms, {}, 6, 5)]
    rounds = [(a0, 8, 10), (b0, 16, 10)]

    def read(name, spans, rounds=rounds):
        return R.reader(name)(Readings(trace, spans, calls, conf, {},
                                       rounds))
    spans = window + round_ + reindex
    assert read("reindex_ms.lat", spans) == pytest.approx(3.0)
    assert read("bin_ms_per_tick.lat", spans) == pytest.approx(0.25)
    assert read("sync_ms_per_window.tput", spans) == pytest.approx(0.75)
    # rounds ran and none re-indexed: 0.0; no round, or a program
    # without the round span: nothing to read
    assert read("reindex_ms.tput", window + round_) == 0.0
    assert read("reindex_ms.tput", spans, rounds=[]) is None
    assert read("reindex_ms.tput", window) is None
    # a program without the window's host-phase spans: nothing to read
    assert read("bin_ms_per_tick.tput", window[:1] + round_) is None
    assert read("sync_ms_per_window.lat", window[:2]) is None
