"""Smoke run of the SWARM streaming engine on a TPU.

Drives the main path once through the entry point a user calls,
``repro.streaming.run(Experiment(..., data_plane="jax"))`` with fused
windows, and checks every result against the NumPy reference plane.

    python chip_smoke.py             # one chip: phases range, pubsub, kernels
    python chip_smoke.py --chips 4   # four chips: range on the sharded plane
                                     # at D=4 against the jax plane, only

Phases:

* ``range``: SWARM on G=64, 8 machines, 100,000 resident range queries,
  131,072 tuples per tick for 64 ticks, rounds every 8 ticks, fused
  windows of 8.  Per-tick ``injected`` and every rebalance round with its
  transfers must equal the NumPy plane's; throughput and latency agree
  to rtol 1e-3, latency also within a float32 bound (``F32_SLACK``).
  At least one round must rebalance and at least one fused window must
  be kept from the device.
* ``pubsub``: the spatial-keyword deployment of ``benchmarks/pubsub.py``
  (1,000,000 standing subscriptions, 32 term buckets, hot hashtags),
  checked against the NumPy plane the same way.  That deployment
  saturates, so backpressure declines its fused windows and the host
  replays them; the same deployment below saturation must keep fused
  keyword windows from the device, checked the same way.
* ``kernels``: each streaming Pallas kernel compiled once at real width
  and checked against its ``ref.py``.

Every line before the last is a human-readable report.  The last line
of stdout is one JSON object, ``{"ok": ..., "device": {"platform",
"kind", "count"}}``.  Without a TPU the script exits non-zero before
running anything, and prints no result.  Everything runs in this one
process: a chip belongs to one process at a time.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

G, M = 64, 8
RANGE_TICKS, RANGE_QUERIES, RANGE_BATCH = 64, 100_000, 131_072
# machine capacity in work units per tick, per tuple of the batch: the
# busiest machine runs at about 0.7 of it before the hotspot, so no
# queue builds, backpressure stays off and every window can fuse
RANGE_CAP_PER_TUPLE = 300.0
PUBSUB_SUBS, PUBSUB_TICKS, PUBSUB_LAMBDA = 1_000_000, 60, 20_000
# machine capacity per subscription: 0.75 is BENCH_pubsub.json's
# saturated deployment; at 64 the busiest machine peaks at about two
# thirds of it, so backpressure stays off and the keyword windows are
# kept
PUBSUB_CAP_PER_SUB, PUBSUB_KEPT_CAP_PER_SUB = 0.75, 64.0
KERNEL_QUERIES, KEYWORD_POINTS = 2048, 16_384
SEED = 0
RTOL = 1e-3
# The device plane steps the queues in float32, the NumPy plane in
# float64.  A machine that empties its queue qu keeps a float32 residual
# of at most eps32·qu (avg = qu/qt and pt·avg each round once, by half
# an ulp), and latency adds queue/capacity: so on a tick the two
# latencies may also differ by up to eps32 times the busiest machine's
# utilization.  When no queue builds, latency is ~1e-5 ticks and that
# residual is a few 1e-3 of it, beyond rtol alone.  F32_SLACK is the
# margin over that bound.
F32_SLACK = 2.0


def log(*parts) -> None:
    print(*parts, flush=True)


def require_tpu():
    """The TPU devices JAX sees; exits non-zero when there are none."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX finds no TPU (platform "
                 f"{devs[0].platform!r}); refusing to run on the CPU")
    return devs


# ---------------------------------------------------------------------------
# engine phases
# ---------------------------------------------------------------------------

def range_experiment(plane: str, devices: int = 0):
    from repro.streaming import (EngineConfig, Experiment, RouterSpec,
                                 ScenarioSpec, TelemetryConfig)
    return Experiment(
        router=RouterSpec("swarm", grid_size=G, beta=8),
        scenario=ScenarioSpec("uniform_normal", ticks=RANGE_TICKS,
                              preload_queries=RANGE_QUERIES, query_burst=0),
        engine=EngineConfig(num_machines=M, round_every=8, fused_window=8,
                            lambda_max=RANGE_BATCH,
                            cap_units=RANGE_CAP_PER_TUPLE * RANGE_BATCH,
                            devices=devices,
                            telemetry=TelemetryConfig(tick_spans=False)),
        seed=SEED, data_plane=plane)


def pubsub_experiment(plane: str, cap_per_sub: float):
    from repro.queries import WorkloadSpec
    from repro.streaming import (EngineConfig, Experiment, RouterSpec,
                                 ScenarioSpec, TelemetryConfig)
    return Experiment(
        router=RouterSpec("swarm", grid_size=G, history_seed=1),
        scenario=ScenarioSpec("hot_hashtags", ticks=PUBSUB_TICKS,
                              preload_queries=PUBSUB_SUBS, query_burst=0,
                              hot_terms=2, term_peak=0.5),
        workload=WorkloadSpec(query_model="spatial_keyword",
                              term_buckets=32),
        engine=EngineConfig(num_machines=M,
                            cap_units=cap_per_sub * PUBSUB_SUBS,
                            lambda_max=PUBSUB_LAMBDA, mem_queries=10**8,
                            fused_window=8,
                            telemetry=TelemetryConfig(tick_spans=False)),
        seed=SEED, data_plane=plane)


def rounds(result) -> list:
    """Every rebalance round with its transfers, wall-clock free."""
    return [(rec.round_no, rec.kind, rec.decision,
             tuple((t.m_h, t.m_l, t.action, tuple(t.moved_pids),
                    tuple(t.new_pids), t.moved_queries)
                   for t in rec.transfers))
            for rec in result.router.swarm.decision_log]


def window_report(result) -> dict:
    """Fused windows kept against declined ones (the flight recorder's
    ``fused_window`` span carries ``ok``; a declined window ran on the
    device, but backpressure began inside it and the host replayed it),
    the window programs dispatched to the device, and their compile and
    dispatch time."""
    spans = [e for e in result.tracer.events if e.kind == "span"]
    fused = [e.args.get("ok") for e in spans if e.name == "fused_window"]

    def total_s(*names):
        return sum(e.dur for e in spans if e.name in names) / 1e9

    return {"fused": sum(1 for ok in fused if ok),
            "declined": sum(1 for ok in fused if not ok),
            "dispatched": sum(1 for e in spans if e.name in (
                "fused_window_compile", "fused_window_dispatch",
                "sharded_window_compile", "sharded_window_dispatch")
                and e.args.get("plane") in ("jax", "sharded")),
            "compile_s": total_s("fused_window_compile",
                                 "sharded_window_compile"),
            "dispatch_s": total_s("fused_window_dispatch",
                                  "sharded_window_dispatch"),
            "round_closes": sum(1 for e in spans if e.name == "round_close")}


def compare(name: str, ref, got, checks: list) -> None:
    """Append (label, passed, detail) for the plane-parity contract."""
    a, b = ref.metrics, got.metrics
    checks.append((f"{name}: per-tick injected identical",
                   a.injected == b.injected,
                   f"{sum(a.injected)} vs {sum(b.injected)} tuples"))
    ra, rb = rounds(ref), rounds(got)
    checks.append((f"{name}: rebalance rounds and transfers identical",
                   ra == rb, f"{len(ra)} vs {len(rb)} rounds"))
    checks.append((f"{name}: per-tick transfers identical",
                   a.transfers == b.transfers, f"{sum(a.transfers)} total"))
    busiest = np.asarray(a.utilization, np.float64).max(axis=1)
    f32 = F32_SLACK * float(np.finfo(np.float32).eps) * busiest
    for metric, atol in (("throughput", np.zeros_like(f32)),
                          ("latency", f32)):
        x = np.asarray(getattr(a, metric), np.float64)
        y = np.asarray(getattr(b, metric), np.float64)
        diff = np.abs(x - y)
        bound = atol + RTOL * np.abs(x)
        # share of its bound the worst tick uses (1.0 is the limit; a
        # difference where the bound is 0 uses all of it and more)
        with np.errstate(divide="ignore", invalid="ignore"):
            used = float(np.max(np.where(diff > 0, diff / bound, 0.0)))
        rel = float(np.max(np.where(x != 0, diff / np.abs(x), 0.0)))
        label = f"rtol {RTOL}" + (" + float32 bound" if metric == "latency"
                                  else "")
        checks.append((f"{name}: {metric} within {label}", used <= 1.0,
                       f"worst tick uses {used:.3f} of it; largest "
                       f"|diff| {diff.max():.3e}, relative {rel:.3e}"))


def run_pair(name: str, build, planes: tuple[str, str], checks: list,
             kept: bool = True):
    """Run one experiment on the reference plane and the device plane,
    report both, and compare them.  ``kept``: at least one fused window
    must be kept from the device, not declined and replayed."""
    from repro.streaming import run
    from repro.telemetry import Stopwatch
    results = []
    for plane in planes:
        with Stopwatch() as sw:
            res = run(build(plane))
        m = res.metrics
        rep = window_report(res)
        log(f"[{name}] plane={plane} wall_s={sw.s:.3f} "
            f"ticks={len(m.injected)} injected={sum(m.injected)} "
            f"transfers={sum(m.transfers)} "
            f"migration_bytes={sum(m.migration_bytes)} "
            f"max_utilization={float(np.max(m.utilization)):.4f} "
            f"fused_windows={rep['fused']} declined={rep['declined']} "
            f"device_windows={rep['dispatched']} "
            f"window_compile_s={rep['compile_s']:.3f} "
            f"window_dispatch_s={rep['dispatch_s']:.3f} "
            f"round_closes={rep['round_closes']}")
        results.append((res, rep))
    (ref, _), (got, rep) = results
    compare(name, ref, got, checks)
    checks.append((f"{name}: at least one rebalancing round",
                   sum(got.metrics.transfers) > 0,
                   f"{sum(got.metrics.transfers)} transfers"))
    checks.append((f"{name}: window program ran on the device",
                   rep["dispatched"] > 0,
                   f"{rep['dispatched']} dispatches"))
    if kept:
        checks.append((f"{name}: fused windows kept from the device",
                       rep["fused"] > 0,
                       f"{rep['fused']} fused, {rep['declined']} declined"))
    return got


def phase_range(checks: list) -> None:
    run_pair("range", range_experiment, ("numpy", "jax"), checks)


def phase_pubsub(checks: list) -> None:
    # the deployment saturates, so backpressure may decline every window
    # and the host replays it: its parity is required, kept windows not
    run_pair("pubsub",
             lambda p: pubsub_experiment(p, PUBSUB_CAP_PER_SUB),
             ("numpy", "jax"), checks, kept=False)
    # below saturation the keyword windows are kept from the device
    run_pair("pubsub/unsaturated",
             lambda p: pubsub_experiment(p, PUBSUB_KEPT_CAP_PER_SUB),
             ("numpy", "jax"), checks)


def phase_range_sharded(chips: int, checks: list) -> None:
    """Phase ``range`` on the sharded plane at D=chips against the jax
    plane; the transfers physically reshard across the chips."""
    name = f"range/sharded[D={chips}]"
    got = run_pair(
        name, lambda p: range_experiment(p, chips if p == "sharded" else 0),
        ("jax", "sharded"), checks)
    plane = got.router.plane
    billed = int(sum(got.metrics.migration_bytes))
    checks.append((f"{name}: mesh spans {chips} chips",
                   plane.devices == chips, f"{plane.devices} devices"))
    checks.append((f"{name}: reshard bytes == billed",
                   plane.reshard_bytes_total == billed,
                   f"{plane.reshard_bytes_total} vs {billed} bytes"))


# ---------------------------------------------------------------------------
# kernels phase
# ---------------------------------------------------------------------------

def _stats_inputs(rng):
    # integer-valued channels with every prefix sum below 2^24: the fold
    # is exact, so the kernel must equal the reference bit for bit
    bank = rng.integers(0, 16_000, (8, 1024, 1001)).astype(np.float32)
    return (bank,)


def _points(rng, n):
    return rng.random((n, 2), dtype=np.float32)


def _rects(rng):
    lo = rng.random((KERNEL_QUERIES, 2), dtype=np.float32) * 0.9
    side = rng.uniform(0.01, 0.1, (KERNEL_QUERIES, 2)).astype(np.float32)
    return np.concatenate([lo, lo + side], 1)


def _spatial_inputs(rng):
    return _points(rng, RANGE_BATCH), _rects(rng)


def _keyword_inputs(rng):
    # tuples carry ~half the buckets, subscriptions ~2 of 32: both sides
    # of the conjunction occur
    pm = (rng.random((KEYWORD_POINTS, 32)) < 0.5).astype(np.float32)
    sm = (rng.random((KERNEL_QUERIES, 32)) < 0.06).astype(np.float32)
    return _points(rng, KEYWORD_POINTS), pm, _rects(rng), sm


def _knn_inputs(rng):
    return _points(rng, RANGE_BATCH), _points(rng, KERNEL_QUERIES)


def kernel_table():
    """(name, kernel, jitted reference, inputs, exact) per streaming
    kernel."""
    import functools

    import jax

    from repro.kernels.keyword_match import keyword_match, keyword_match_ref
    from repro.kernels.knn_match import knn_match, knn_match_ref
    from repro.kernels.spatial_match import spatial_match, spatial_match_ref
    from repro.kernels.stats_update import close_round, close_round_ref
    return [
        ("stats_update", close_round, jax.jit(close_round_ref),
         _stats_inputs, True),
        ("spatial_match", spatial_match, jax.jit(spatial_match_ref),
         _spatial_inputs, True),
        ("keyword_match", keyword_match, jax.jit(keyword_match_ref),
         _keyword_inputs, True),
        ("knn_match", functools.partial(knn_match, k=8),
         jax.jit(functools.partial(knn_match_ref, k=8)), _knn_inputs,
         False),
    ]


def phase_kernels(checks: list) -> None:
    import jax

    from repro.telemetry import Stopwatch
    rng = np.random.default_rng(SEED)
    for name, kernel, ref, inputs, exact in kernel_table():
        args = [jax.device_put(a) for a in inputs(rng)]
        with Stopwatch() as compile_sw:     # one compile per kernel, timed
            compiled = jax.jit(kernel).lower(*args).compile()  # swarmlint: disable=SWM001
        with Stopwatch() as run_sw:
            out = jax.block_until_ready(compiled(*args))
        want = ref(*args)
        outs = out if isinstance(out, tuple) else (out,)
        wants = want if isinstance(want, tuple) else (want,)
        if exact:
            ok = all(np.array_equal(np.asarray(o), np.asarray(w))
                     for o, w in zip(outs, wants))
        else:
            ok = all(np.allclose(np.asarray(o), np.asarray(w), rtol=1e-5,
                                 atol=1e-6) for o, w in zip(outs, wants))
        has_kernel = "tpu_custom_call" in compiled.as_text()
        log(f"[kernels] {name} shapes={[tuple(a.shape) for a in args]} "
            f"out_sum={float(np.sum(np.asarray(outs[0], np.float64))):.6g} "
            f"compile_s={compile_sw.s:.3f} first_call_s={run_sw.s:.4f} "
            f"tpu_custom_call={has_kernel} "
            f"{'exact' if exact else 'allclose'}={ok}")
        checks.append((f"kernels: {name} matches ref.py", ok, ""))
        checks.append((f"kernels: {name} compiled to tpu_custom_call",
                       has_kernel, ""))


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run phase range on the sharded plane at D=4 "
                         "against the jax plane, and nothing else")
    args = ap.parse_args(argv)
    devs = require_tpu()
    if len(devs) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"TPU devices, JAX sees {len(devs)}")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.mesh import use_compile_cache
    from repro.telemetry import Stopwatch
    cache = use_compile_cache()
    log(f"device: {devs[0].platform} {devs[0].device_kind} x{len(devs)}; "
        f"compile cache: {cache}")
    checks: list = []
    total = Stopwatch().start()
    if args.chips == 4:
        phases = [("range/sharded", lambda: phase_range_sharded(4, checks))]
    else:
        phases = [("range", lambda: phase_range(checks)),
                  ("pubsub", lambda: phase_pubsub(checks)),
                  ("kernels", lambda: phase_kernels(checks))]
    for name, phase in phases:
        with Stopwatch() as sw:
            try:
                phase()
            except Exception as e:  # report the phase, run the others
                import traceback
                traceback.print_exc()
                checks.append((f"{name}: phase ran", False, repr(e)))
        log(f"[{name}] phase wall_s={sw.s:.3f}")
    for label, ok, detail in checks:
        log(f"{'PASS' if ok else 'FAIL'} {label}"
            + (f" ({detail})" if detail else ""))
    ok = bool(checks) and all(c[1] for c in checks)
    n_cache = (sum(len(f) for _, _, f in os.walk(cache))
               if os.path.isdir(cache) else 0)
    log(f"total wall_s={total.stop().s:.3f}; "
        f"{sum(c[1] for c in checks)}/{len(checks)} checks passed; "
        f"compile cache entries: {n_cache}")
    print(json.dumps({"ok": ok, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
