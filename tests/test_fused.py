"""Device-resident fused ingest: cross-plane step/window parity with
the per-tick reference loop, rebalance rounds and machine failures at
window boundaries, store-workload rejection, and scan-window-size
metric invariance."""
import json
import os
import pathlib
import subprocess
import sys

import numpy as np
import pytest

from repro.core import statistics as S
from repro.queries import WorkloadSpec
from repro.streaming import (EngineConfig, Experiment, RouterSpec,
                             ScenarioSpec, StreamingEngine, SwarmRouter,
                             get_plane, run, scenario)

G, M = 64, 8

# capacity high enough that backpressure stays idle: with it engaged the
# per-tick loop draws n < λmax samples per tick while the fused path
# stages full batches and masks, so the RNG streams (not the dynamics)
# would diverge — the documented window-staging semantics
CFG = EngineConfig(num_machines=M, cap_units=1e9, lambda_max=2000,
                   mem_queries=10**8, round_every=3)
# ticks=12 ⇒ hotspot query burst at ticks 4–7 (arrival boundaries) and
# rebalance rounds at 3, 6, 9 — i.e. rounds *inside* scan windows
SCEN = ScenarioSpec("uniform_normal", ticks=12, preload_queries=500,
                    query_burst=200)


def _run_pair(plane: str, seed: int = 0, window: int = 8, cfg=CFG,
              scen=SCEN):
    base = Experiment(router=RouterSpec("swarm", beta=4), scenario=scen,
                      engine=cfg, data_plane=plane, seed=seed)
    import dataclasses
    fused = base.with_(engine=dataclasses.replace(cfg, fused_window=window))
    return run(base).metrics.asarrays(), run(fused).metrics.asarrays()


# ---------------------------------------------------------------------------
# run_fused ≡ per-tick loop
# ---------------------------------------------------------------------------

def test_run_fused_matches_per_tick_numpy_exactly():
    ref, fused = _run_pair("numpy")
    for name in ref:
        np.testing.assert_array_equal(ref[name], fused[name], err_msg=name)


def test_run_fused_matches_per_tick_jax():
    ref, fused = _run_pair("jax")
    np.testing.assert_array_equal(ref["injected"], fused["injected"])
    np.testing.assert_array_equal(ref["q_total"], fused["q_total"])
    np.testing.assert_array_equal(ref["transfers"], fused["transfers"])
    for name in ("units_of_work", "throughput", "latency", "utilization",
                 "wire_bytes", "migration_bytes"):
        np.testing.assert_allclose(
            np.asarray(ref[name], np.float64),
            np.asarray(fused[name], np.float64),
            rtol=1e-3, atol=1e-6, err_msg=name)


@pytest.mark.parametrize("plane", ["numpy", "jax"])
def test_run_fused_backpressure_falls_back_to_reference(plane):
    # tiny capacity: backpressure throttles injection mid-run.  The
    # NumPy plane handles throttled injection inside its window; the
    # JAX plane's optimistic window *declines* (ok=False) and the
    # engine replays the staged batches through
    # StreamingEngine._window_reference — this pins both.  The
    # *streams* legitimately diverge (the per-tick loop draws n < λmax
    # samples, the fused path masks a staged full batch — documented
    # window-staging semantics), but the dynamics must agree: identical
    # per-tick injection counts and finite, same-shape metrics.
    cfg = EngineConfig(num_machines=M, cap_units=3e3, lambda_max=2000,
                       mem_queries=10**8, round_every=3)
    ref, fused = _run_pair(plane, cfg=cfg)
    assert min(ref["injected"]) < 2000          # throttling engaged
    np.testing.assert_array_equal(ref["injected"], fused["injected"])
    np.testing.assert_array_equal(ref["q_total"], fused["q_total"])
    for name in ("units_of_work", "throughput", "latency"):
        arr = np.asarray(fused[name], np.float64)
        assert np.isfinite(arr).all() and arr.shape == ref[name].shape
        # same workload distribution: aggregate work within a few %
        np.testing.assert_allclose(arr.sum(), ref[name].sum(), rtol=0.2)


@pytest.mark.parametrize("plane", ["numpy", "jax"])
@pytest.mark.parametrize("seed", [0, 3])
def test_window_size_invariance(plane, seed):
    """W is an execution-granularity knob, not a semantics knob: W=1
    and W=32 must produce the same metrics (exactly on the reference
    plane; float32 aggregation tolerance on JAX)."""
    a = _run_pair(plane, seed=seed, window=1)[1]
    b = _run_pair(plane, seed=seed, window=32)[1]
    for name in a:
        if plane == "numpy":
            np.testing.assert_array_equal(a[name], b[name], err_msg=name)
        else:
            np.testing.assert_allclose(
                np.asarray(a[name], np.float64),
                np.asarray(b[name], np.float64),
                rtol=1e-4, atol=1e-7, err_msg=name)


def test_window_size_invariance_hypothesis():
    pytest.importorskip("hypothesis")  # dev extra (pyproject.toml)
    from hypothesis import given, settings, strategies as st

    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 10**6), w=st.integers(1, 16))
    def check(seed, w):
        a = _run_pair("numpy", seed=seed, window=w)[1]
        b = _run_pair("numpy", seed=seed, window=7)[1]
        for name in a:
            np.testing.assert_array_equal(a[name], b[name], err_msg=name)

    check()


# ---------------------------------------------------------------------------
# Failure at a window boundary
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("plane", ["numpy", "jax"])
def test_machine_failure_at_window_boundary(plane):
    def drive(fused: bool):
        src = scenario("none", horizon=40, seed=2)
        r = SwarmRouter(G, M, beta=4, data_plane=plane)
        eng = StreamingEngine(r, src, CFG)
        eng.preload_queries(src.sample_queries(400))
        go = (lambda t: eng.run_fused(t, window=8)) if fused else eng.run
        go(8)
        eng.fail_machine(3)
        go(8)
        return eng

    a, b = drive(False), drive(True)
    assert len(b.router.swarm.index.machine_partitions(3)) == 0
    ka, kb = a.metrics.asarrays(), b.metrics.asarrays()
    np.testing.assert_array_equal(ka["injected"], kb["injected"])
    tol = dict(rtol=0, atol=0) if plane == "numpy" \
        else dict(rtol=1e-3, atol=1e-6)
    for name in ("units_of_work", "throughput", "utilization"):
        np.testing.assert_allclose(np.asarray(ka[name], np.float64),
                                   np.asarray(kb[name], np.float64),
                                   err_msg=name, **tol)
    # dead machine takes no further work on either path
    assert np.asarray(kb["utilization"])[-4:, 3].max() == 0.0


# ---------------------------------------------------------------------------
# plane.step: single fused dispatch ≡ reference per-call math
# ---------------------------------------------------------------------------

def test_step_cross_plane_parity_and_collectors():
    rng = np.random.default_rng(7)
    router = SwarmRouter(G, M, beta=4)
    router.register_queries(
        np.clip(rng.uniform(0, 0.95, (300, 4)), 0, 0.999)
        .astype(np.float32))
    host = router.fused_host_state()
    cp = router._cost_params()
    xy = rng.uniform(0, 1, (1000, 2)).astype(np.float32)

    np_plane, jx_plane = get_plane("numpy"), get_plane("jax")
    st_n = np_plane.make_state(host)
    st_j = jx_plane.make_state(host)
    st_n, (pids_n, own_n, cost_n) = np_plane.step(st_n, cp, xy,
                                                  track_stats=True)
    st_j, (pids_j, own_j, cost_j) = jx_plane.step(st_j, cp, xy,
                                                  track_stats=True)
    np.testing.assert_array_equal(pids_n, pids_j)
    np.testing.assert_array_equal(own_n, own_j)
    np.testing.assert_allclose(cost_n.astype(np.float64), cost_j,
                               rtol=1e-4, atol=1e-7)
    # collector banks: integer counts, exact across planes, and equal
    # to what the host-side ingest would have accumulated
    np.testing.assert_array_equal(np.asarray(st_j.cn_rows), st_n.cn_rows)
    np.testing.assert_array_equal(np.asarray(st_j.cn_cols), st_n.cn_cols)
    before = router.swarm.stats.rows[S.C_N].copy()
    router.swarm.ingest_points(xy)
    delta = router.swarm.stats.rows[S.C_N] - before
    np.testing.assert_array_equal(st_n.cn_rows[:delta.shape[0]],
                                  delta[:st_n.cn_rows.shape[0]])


def test_step_rejects_query_batches():
    router = SwarmRouter(G, M)
    host = router.fused_host_state()
    plane = get_plane("numpy")
    st = plane.make_state(host)
    with pytest.raises(NotImplementedError, match="host-boundary"):
        plane.step(st, router._cost_params(), np.zeros((4, 2), np.float32),
                   query_batch=np.zeros((1, 4), np.float32))


# ---------------------------------------------------------------------------
# Scatter patching and guards
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("plane", ["numpy", "jax"])
def test_scatter_update_patches_device_state(plane):
    router = SwarmRouter(G, M)
    host = router.fused_host_state()
    pl = get_plane(plane)
    st = pl.make_state(host)
    # simulate a rebalance: a few owner rows and grid cells change
    new_owner = host.owner.copy()
    new_owner[[2, 5]] = [7, 1]
    new_grid = host.grid.copy()
    new_grid[0, :5] = 3
    import dataclasses
    updates = host.diff(dataclasses.replace(host, owner=new_owner,
                                            grid=new_grid))
    st = pl.scatter_update(st, updates)
    np.testing.assert_array_equal(np.asarray(st.owner), new_owner)
    np.testing.assert_array_equal(np.asarray(st.grid), new_grid)


@pytest.mark.parametrize("persistence", ["ephemeral", "stored"])
def test_snapshot_workloads_fuse_between_probe_arrivals(persistence):
    """Store-keeping workloads run fused: probes arrive on the sources'
    deterministic ``snapshot_every`` schedule (window boundaries), the
    engine replays each window's deposits into the host-side store, and
    the metrics match the per-tick reference exactly."""
    import dataclasses

    from repro.streaming import Experiment, RouterSpec, ScenarioSpec, run
    wl = WorkloadSpec(query_model="snapshot", persistence=persistence,
                      snapshot_rate=100)
    spec = ScenarioSpec("none", ticks=16, preload_queries=0, query_burst=0,
                        snapshot_every=4)
    base = Experiment(router=RouterSpec("swarm", beta=4), scenario=spec,
                      engine=CFG, workload=wl)
    fused = base.with_(engine=dataclasses.replace(CFG, fused_window=8))
    ref = run(base).metrics.asarrays()
    out = run(fused).metrics.asarrays()
    for name in ref:
        np.testing.assert_array_equal(ref[name], out[name], err_msg=name)
    assert np.asarray(ref["snapshots"]).max() > 0   # probes did arrive


def test_run_fused_rejects_routers_without_seam():
    from repro.streaming import ReplicatedRouter
    src = scenario("none", horizon=4)
    eng = StreamingEngine(ReplicatedRouter(M, G), src, CFG)
    with pytest.raises(ValueError, match="fused_host_state"):
        eng.run_fused(2)


def test_engine_benchmark_smoke_counts_agree():
    pytest.importorskip("benchmarks.engine_throughput")
    # a fresh process, so the devices axis gets two forced host devices
    # (jax fixes its device count when its backend first starts)
    root = pathlib.Path(__file__).resolve().parents[1]
    child = ("import json, sys\n"
             "from repro.launch.mesh import force_host_device_count\n"
             "force_host_device_count(2)\n"
             "from benchmarks import engine_throughput as bench\n"
             "res = bench.run(smoke=True)\n"
             "print(json.dumps(res))\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(root / "src"), str(root)]))
    out = subprocess.run([sys.executable, "-c", child], cwd=root, env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["results"][0]["counts_equal"]
    # the sharded axis ran at D=1 and D=2, each count-identical to jax
    assert [r["devices"] for r in res["devices"]] == [1, 2]
    assert all(r.get("counts_equal") for r in res["devices"])
