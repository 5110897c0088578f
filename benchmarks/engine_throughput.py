"""End-to-end engine ingest throughput: the per-tick event loop vs the
device-resident fused path (``StreamingEngine.run_fused``), on both
data planes (BENCH_engine.json).

Setup: a live ``SwarmRouter`` (rounds every ``ROUND_EVERY`` ticks, so
the adaptivity protocol runs at its normal cadence inside the measured
region), 2000 resident queries, and a ``ReplaySource`` point pool so
source synthesis stays off the measured path.  Timings exclude a
warm-up long enough to cover several rounds (jit compilation and the
first rebalances); events/sec counts injected tuples.

The harness *asserts* that fused and per-tick modes inject identical
per-tick tuple counts before timing anything — the throughput numbers
cannot silently diverge from the correctness of the fused semantics.

The multi-device axis (``results["devices"]``) times the sharded plane
at several device counts, all in this one process (``sharded_plane(d)``
over ``jax.devices()[:d]``): a chip belongs to one process, so a child
process could not reach it.  Each count first asserts sharded-vs-jax
count identity; a count above the visible devices is reported as not
run.  On a CPU host, ``XLA_FLAGS=--xla_force_host_platform_device_count``
(``launch.mesh.force_host_device_count``, set before jax initializes)
provides the devices.
"""
from __future__ import annotations

import json
import os

import jax
import numpy as np

from repro.streaming import (EngineConfig, ReplaySource, StreamingEngine,
                             SwarmRouter, TwitterLikeSource)
from repro.telemetry import Stopwatch

from .common import emit

G, M = 64, 8
ROUND_EVERY = 8
WINDOW = 8
ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))
OUT_JSON = os.path.join(ROOT, "BENCH_engine.json")


def _engine(plane, batch: int, pool: np.ndarray, *,
            devices: int = 0) -> StreamingEngine:
    cfg = EngineConfig(num_machines=M, cap_units=1e12,
                       lambda_max=float(batch), mem_queries=10**9,
                       round_every=ROUND_EVERY)
    base = TwitterLikeSource(seed=1)
    # the sharded plane histograms at ingest: give the source the grid
    cell_grid = G if plane == "sharded" else 0
    src = ReplaySource(pool=pool, base=base, cell_grid=cell_grid)
    if plane == "sharded":
        from repro.streaming.sharded import sharded_plane
        plane = sharded_plane(devices or None)
    eng = StreamingEngine(SwarmRouter(G, M, beta=8, data_plane=plane),
                          src, cfg)
    eng.preload_queries(base.sample_queries(2000))
    return eng


def _events_per_s(plane, batch: int, pool: np.ndarray, fused: bool,
                  warm: int, ticks: int, *, devices: int = 0) -> float:
    eng = _engine(plane, batch, pool, devices=devices)
    runner = (lambda t: eng.run_fused(t, window=WINDOW)) if fused \
        else eng.run
    runner(warm)
    with Stopwatch() as sw:
        runner(ticks)
    return sum(eng.metrics.injected[-ticks:]) / sw.s


def _assert_counts_equal(plane: str, batch: int, pool: np.ndarray,
                         ticks: int) -> None:
    """Fused and per-tick modes must report identical per-tick tuple
    counts (and matching processed totals) on identical streams."""
    a = _engine(plane, batch, pool)
    a.run(ticks)
    b = _engine(plane, batch, pool)
    b.run_fused(ticks, window=WINDOW)
    if a.metrics.injected != b.metrics.injected:
        raise AssertionError(
            f"fused/per-tick injected counts diverged on {plane}: "
            f"{a.metrics.injected} vs {b.metrics.injected}")
    if not np.allclose(a.metrics.throughput, b.metrics.throughput,
                       rtol=1e-3, atol=1e-6):
        raise AssertionError(
            f"fused/per-tick processed totals diverged on {plane}")


def _device_cell(d: int, batch: int, pool: np.ndarray, warm: int,
                 ticks: int, check_ticks: int = 12) -> dict:
    """One sharded measurement at ``d`` devices.

    Asserts count identity against the single-device jax fused plane
    *before* timing: the same stream must inject identical per-tick
    counts and matching processed totals (spans a rebalance round)."""
    a = _engine("jax", batch, pool)
    a.run_fused(check_ticks, window=WINDOW)
    b = _engine("sharded", batch, pool, devices=d)
    b.run_fused(check_ticks, window=WINDOW)
    if a.metrics.injected != b.metrics.injected:
        raise AssertionError(
            f"sharded/jax injected counts diverged at devices={d}: "
            f"{a.metrics.injected} vs {b.metrics.injected}")
    if not np.allclose(a.metrics.throughput, b.metrics.throughput,
                       rtol=1e-3, atol=1e-6):
        raise AssertionError(
            f"sharded/jax processed totals diverged at devices={d}")
    evps = _events_per_s("sharded", batch, pool, True, warm, ticks,
                         devices=d)
    return {"devices": d, "batch": batch, "sharded_fused_evps": evps,
            "counts_equal": True}


def run(smoke: bool = False) -> dict:
    sizes = (4096,) if smoke else (1 << 14, 1 << 17)
    warm, ticks = (8, 8) if smoke else (40, 24)
    pool = TwitterLikeSource(seed=0).sample_points(1 << 20)
    rows = []
    for batch in sizes:
        row: dict = {"batch": batch, "ticks": ticks}
        for plane in ("numpy", "jax"):
            _assert_counts_equal(plane, batch, pool, min(ticks, 12))
            for fused in (False, True):
                mode = "fused" if fused else "pertick"
                evps = _events_per_s(plane, batch, pool, fused, warm, ticks)
                row[f"{plane}_{mode}_evps"] = evps
                emit(f"engine/{plane}/{mode}/batch={batch}",
                     1e6 / evps, f"events_per_s={evps:.0f}")
        row["fused_jax_vs_pertick_jax"] = (row["jax_fused_evps"]
                                           / row["jax_pertick_evps"])
        row["fused_jax_vs_pertick_numpy"] = (row["jax_fused_evps"]
                                             / row["numpy_pertick_evps"])
        row["counts_equal"] = True
        emit(f"engine/summary/batch={batch}", 0.0,
             f"fused_jax_vs_pertick_jax="
             f"{row['fused_jax_vs_pertick_jax']:.2f}x "
             f"vs_pertick_numpy={row['fused_jax_vs_pertick_numpy']:.2f}x")
        rows.append(row)
    # multi-device axis: sharded-plane fused throughput vs device count,
    # at the largest batch, in this process; counts above the visible
    # devices are recorded as not run
    batch = sizes[-1]
    base_evps = rows[-1]["jax_fused_evps"]
    visible = len(jax.devices())
    dev_rows = []
    for d in ((1, 2) if smoke else (1, 2, 4, 8)):
        if d > visible:
            dev_rows.append({"devices": d, "batch": batch,
                             "not_run": f"{visible} devices visible"})
            print(f"# engine/sharded/devices={d}/batch={batch}: not run, "
                  f"{visible} devices visible")
            continue
        cell = _device_cell(d, batch, pool, warm, ticks)
        cell["speedup_vs_jax_fused"] = cell["sharded_fused_evps"] / base_evps
        emit(f"engine/sharded/devices={d}/batch={batch}",
             1e6 / cell["sharded_fused_evps"],
             f"events_per_s={cell['sharded_fused_evps']:.0f} "
             f"speedup_vs_jax_fused={cell['speedup_vs_jax_fused']:.2f}x")
        dev_rows.append(cell)
    # forced host devices time-slice the physical cores: with fewer
    # cores than devices the D>1 cells measure collective overhead, not
    # scaling — record the host width so the axis reads honestly
    result = {"grid": G, "machines": M, "round_every": ROUND_EVERY,
              "window": WINDOW, "smoke": smoke, "host_cpus": os.cpu_count(),
              "results": rows, "devices": dev_rows}
    if not smoke:
        with open(OUT_JSON, "w") as f:
            json.dump(result, f, indent=1)
    return result
