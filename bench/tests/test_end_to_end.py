"""Result-latency percentiles, events/s and the open loop's due-time
accounting, over synthetic schedules."""
import math
import time

import numpy as np

import run as R

B = 1000


def schedule(n=200, period=0.01, base=0.002, stall_at=100, stall=0.2):
    """Tick k due at (k+1)·period, done ``base`` later; a ``stall``
    at tick ``stall_at`` delays it and every tick queued behind it."""
    due = (np.arange(n) + 1) * period
    done = due + base
    t_free = 0.0
    for k in range(n):
        start = max(due[k], t_free)
        if k == stall_at:
            start += stall
        done[k] = start + base
        t_free = done[k]
    return {"n_ticks": n, "handed": n, "due_at": due, "done_at": done,
            "t_end": n * period}


def test_tail_sees_the_stall_and_median_does_not():
    d = schedule()
    vals, attempted, failed = R.end_to_end(d, np.full(200, B), B, 2.0)
    assert math.isclose(vals["result_p50_ms"], 2.0, rel_tol=1e-6)
    # the stall delays its tick by 200 ms, and the 20 queued behind it
    assert vals["result_p95_ms"] > 100.0
    assert attempted == 200 * B and failed == 0


def test_rate_counts_only_results_inside_the_window():
    d = schedule(stall=0.0)
    d["t_end"] = d["done_at"][149] + 1e-9
    vals, _, _ = R.end_to_end(d, np.full(200, B), B, 1.5)
    assert math.isclose(vals["events_per_s"], 150 * B / 1.5)
    # a stall late in the window pushes results past its close
    d = schedule(stall_at=180, stall=0.5)
    vals_stall, _, _ = R.end_to_end(d, np.full(200, B), B, 2.0)
    vals_free, _, _ = R.end_to_end(schedule(stall=0.0), np.full(200, B),
                                   B, 2.0)
    assert vals_stall["events_per_s"] < vals_free["events_per_s"]


def test_missing_and_shed_events_fail_and_count_beyond_every_limit():
    d = schedule(stall=0.0)
    d["done_at"][-20:] = np.nan          # never arrived
    inj = np.full(200, B)
    inj[:5] = B // 2                     # backpressure shed half
    vals, attempted, failed = R.end_to_end(d, inj, B, 2.0)
    assert failed == 20 * B + 5 * (B // 2)
    assert vals["result_p95_ms"] == math.inf
    assert math.isclose(vals["result_p50_ms"], 2.0, rel_tol=1e-6)


def test_nearest_rank():
    v = np.arange(1, 101, dtype=float)
    assert R.nearest_rank(v, 50) == 50.0
    assert R.nearest_rank(v, 95) == 95.0
    assert R.nearest_rank(v, 100) == 100.0


class FakeEngine:
    """Takes 1 ms per tick, like a host loop that keeps up."""

    def __init__(self):
        self.tick_no = 0
        self.calls = []

    def run_fused(self, n, window):
        assert 1 <= n <= window
        self.calls.append(n)
        time.sleep(0.001 * n)
        self.tick_no += n


def test_open_loop_hands_every_due_tick_on_schedule():
    eng = FakeEngine()
    rate, batch, seconds = 40_000.0, 1_000, 0.3
    d = R.drive_open(eng, rate, batch, 8, seconds, trace=False)
    n_due = int(seconds * rate / batch)
    assert d["n_ticks"] == n_due == d["handed"] == eng.tick_no
    assert np.allclose(d["due_at"] - d["t0"],
                       (np.arange(n_due) + 1) * batch / rate)
    lat = d["done_at"] - d["due_at"]
    assert (lat >= 0).all() and np.median(lat) < 0.01
    assert max(eng.calls) <= 8


def test_open_loop_never_slows_the_schedule():
    class Slow(FakeEngine):
        def run_fused(self, n, window):
            super().run_fused(n, window)
            if self.tick_no == 5:
                time.sleep(0.1)          # one stall
    eng = Slow()
    d = R.drive_open(eng, 40_000.0, 1_000, 8, 0.3, trace=False)
    lat = d["done_at"] - d["due_at"]
    # ticks due during the stall wait for it, and are then handed
    # together: 100 ms holds 4 ticks at 25 ms each
    assert lat.max() > 0.08
    assert max(eng.calls) >= 4
    assert d["handed"] == d["n_ticks"]
