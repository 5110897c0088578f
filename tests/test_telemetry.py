"""Flight-recorder telemetry: zero-overhead no-op default, planner
DecisionRecords that mirror the applied transfers exactly, same-seed
span-tree/record determinism on both data planes, Perfetto export
against the checked-in schema, the fused compile/dispatch split, the
fused loop's host-phase spans and their copies in a profiler capture,
program builds as events, and the ft-layer heartbeat/failover
events."""
import dataclasses
import glob
import json
import os
import time
from collections import Counter

import numpy as np
import pytest

from repro.core import Swarm
from repro.streaming import (EngineConfig, Experiment, MembershipEvent,
                             RouterSpec, ScenarioSpec, SwarmRouter,
                             TelemetryConfig)
from repro.streaming.baselines import force_rebalance_round
from repro.streaming.experiments import run, safe_label
from repro.telemetry import (CONTROL, NOOP, DecisionRecord, Stopwatch,
                             Tracer, activate, current, time_once_us,
                             time_us, to_chrome_trace, trace_schema,
                             validate_trace_dict, validate_trace_file)

G, M = 64, 8
CFG = EngineConfig(num_machines=M, cap_units=1e9, lambda_max=2000,
                   mem_queries=10**8, round_every=3)


def _exp(plane="numpy", telemetry=TelemetryConfig(), scenario=None,
         engine=CFG, **scen_kw):
    scen = scenario or ScenarioSpec("uniform_normal", ticks=24,
                                    preload_queries=400, query_burst=150,
                                    **scen_kw)
    return Experiment(router=RouterSpec("swarm", beta=4), scenario=scen,
                      engine=dataclasses.replace(engine,
                                                 telemetry=telemetry),
                      data_plane=plane)


def _hotspot_round(sw, rng):
    pts = np.concatenate([rng.uniform(0, 1, (500, 2)),
                          rng.uniform(0, 0.25, (2000, 2))]).astype(np.float32)
    sw.ingest_points(pts)
    qc = rng.uniform(0, 0.25, (100, 2)).astype(np.float32)
    sw.ingest_queries(np.concatenate([qc, qc + 0.02], 1))
    return sw.run_round()


# ---------------------------------------------------------------------------
# Tracer unit behaviour
# ---------------------------------------------------------------------------

def test_noop_is_default_and_inert():
    res = run(_exp(telemetry=None))
    assert res.tracer is None            # engine kept the NOOP singleton
    assert NOOP.events == [] and NOOP.decisions == []
    assert NOOP.span("tick") is NOOP.span("x")       # shared null span
    with NOOP.span("tick") as sp:
        assert sp.set(a=1) is sp
    assert current() is NOOP             # nothing left activated


def test_metrics_identical_with_telemetry_on_and_off():
    off = run(_exp(telemetry=None)).asarrays()
    on = run(_exp()).asarrays()
    assert set(off) == set(on)
    for name in off:
        np.testing.assert_array_equal(np.asarray(off[name], np.float64),
                                      np.asarray(on[name], np.float64),
                                      err_msg=name)


def test_span_nesting_and_signature_is_wall_free():
    def drive(tr, sleep):
        with activate(tr):
            with tr.span("round_close", tick=3) as sp:
                time.sleep(sleep)
                with tr.span("plan_round", tick=3):
                    pass
                sp.set(decision=1)
            tr.counter("q_total", 7.0, tick=3)
            tr.instant("rebalance", tick=3, machine=CONTROL)
    a, b = Tracer(), Tracer()
    drive(a, 0.0)
    drive(b, 0.01)                       # different wall, same structure
    assert a.signature() == b.signature()
    sig = a.signature()
    assert ("span", "plan_round", CONTROL, 3, "round_close") in sig
    assert ("counter", "q_total", CONTROL, 3, None, 7.0) in sig
    inner = next(e for e in a.events if e.name == "plan_round")
    outer = next(e for e in a.events if e.name == "round_close")
    assert inner.parent == outer.seq and outer.dur >= inner.dur


def test_activate_restores_previous_tracer():
    tr = Tracer()
    with activate(tr):
        assert current() is tr
        with activate(NOOP):
            assert current() is NOOP
        assert current() is tr
    assert current() is NOOP


def test_timers():
    with Stopwatch() as sw:
        time.sleep(0.005)
    assert 0.004 < sw.s < 0.5 and sw.us == pytest.approx(sw.s * 1e6)
    assert time_us(lambda: None, n=50) < 1e4
    us, out = time_once_us(lambda: 42)
    assert out == 42 and us >= 0


# ---------------------------------------------------------------------------
# Flight recorder: DecisionRecords mirror the protocol exactly
# ---------------------------------------------------------------------------

def test_decision_record_transfers_match_round_report_exactly():
    rng = np.random.default_rng(0)
    sw = Swarm(grid_size=32, num_machines=4, decay=1.0, beta=4)
    rebalances = 0
    for _ in range(15):
        rep = _hotspot_round(sw, rng)
        rec = rep.record
        assert isinstance(rec, DecisionRecord)
        assert rec.decision == rep.decision
        assert rec.r_s == pytest.approx(rep.r_s)
        assert rec.did_rebalance == rep.did_rebalance
        if rep.costs is not None:
            assert tuple(rec.costs) == pytest.approx(tuple(rep.costs))
        mirror = tuple((t.m_h, t.m_l, t.action, t.moved_pids, t.new_pids)
                       for t in rec.transfers)
        applied = tuple((t.m_h, t.m_l, t.action, t.moved_pids, t.new_pids)
                        for t in rep.transfers)
        assert mirror == applied
        if rep.did_rebalance:
            rebalances += 1
            # the chosen pair appears among the considered candidates
            # with the matching outcome
            chosen = [c for c in rec.candidates
                      if c.outcome == rep.action
                      and (c.m_h, c.m_l) == (rep.m_h, rep.m_l)]
            assert chosen and chosen[0].pids
            assert rec.wire_bytes == rep.wire_bytes
            assert rec.moved_tuples == rep.moved_tuples
    assert rebalances >= 2
    assert len(sw.decision_log) == 15    # always-on, tracer or not


def test_skipped_candidates_carry_reasons():
    rng = np.random.default_rng(3)
    sw = Swarm(grid_size=32, num_machines=4, decay=1.0, beta=4)
    reasons = set()
    for _ in range(15):
        rep = _hotspot_round(sw, rng)
        for c in rep.record.candidates:
            if c.outcome == "skip":
                reasons.add(c.reason)
                assert c.reason in ("balanced", "no_partitions",
                                    "no_splittable")


def test_router_enriches_records_with_moved_query_billing():
    res = run(_exp())
    recs = [rec for _, rec in res.tracer.decisions if rec.did_rebalance]
    assert recs, "scenario produced no rebalance"
    for rec in recs:
        assert rec.moved_queries >= 0
        assert rec.migration_bytes >= rec.data_bytes
        assert len(rec.moved_by_transfer) == len(rec.transfers)
        assert sum(t.moved_queries for t in rec.transfers) \
            == rec.moved_queries
    # engine decision log and tracer agree
    assert [r.to_dict() for r in res.router.swarm.decision_log] \
        == [r.to_dict() for _, r in res.tracer.decisions]


def test_forced_rebalance_round_is_recorded():
    r = SwarmRouter(G, M, beta=4)
    rng = np.random.default_rng(0)
    r.swarm.ingest_points(rng.uniform(0, 0.2, (4000, 2)).astype(np.float32))
    qc = rng.uniform(0, 0.2, (300, 2)).astype(np.float32)
    r.swarm.ingest_queries(np.concatenate([qc, qc + 0.02], 1))
    rep = force_rebalance_round(r.swarm)
    rec = r.swarm.decision_log[-1]
    assert rec.kind == "forced" and rec is rep.record


# ---------------------------------------------------------------------------
# Determinism: same seed ⇒ same span tree + records, on both planes
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("plane", ["numpy", "jax"])
def test_same_seed_same_span_tree_and_records(plane):
    def once():
        return run(_exp(plane))
    once()                               # warm jit caches (jax) once
    a, b = once(), once()
    assert a.tracer.signature() == b.tracer.signature()
    assert [(t, r.to_dict()) for t, r in a.tracer.decisions] \
        == [(t, r.to_dict()) for t, r in b.tracer.decisions]
    names = set(a.tracer.span_names())
    assert {"tick", "round_close", "heartbeat_scan"} <= names


def test_decision_records_identical_across_planes():
    dn = [(t, r.to_dict())
          for t, r in run(_exp("numpy")).tracer.decisions]
    dj = [(t, r.to_dict())
          for t, r in run(_exp("jax")).tracer.decisions]
    assert dn == dj


# ---------------------------------------------------------------------------
# Perfetto / JSONL export
# ---------------------------------------------------------------------------

def test_perfetto_export_validates_and_carries_decisions(tmp_path):
    exp = _exp(telemetry=TelemetryConfig(trace_dir=str(tmp_path)))
    res = run(exp)
    stem = safe_label(exp.label)
    jsonl = tmp_path / f"{stem}.jsonl"
    trace = tmp_path / f"{stem}.trace.json"
    assert jsonl.exists() and trace.exists()
    assert validate_trace_file(str(trace)) == []
    doc = json.loads(trace.read_text())
    phases = {e["ph"] for e in doc["traceEvents"]}
    assert {"X", "i", "C", "M"} <= phases
    tick_tracks = {e["tid"] for e in doc["traceEvents"]
                   if e["ph"] == "X" and e["name"] == "tick" and e["pid"] == 1}
    assert tick_tracks == set(range(M))  # one track per machine
    decisions = [e for e in doc["traceEvents"]
                 if e.get("cat") == "decision"]
    rebal = [d for d in decisions if d["args"]["transfers"]]
    assert len(decisions) == len(res.tracer.decisions) and rebal
    lines = [json.loads(ln) for ln in jsonl.read_text().splitlines()]
    dlines = [ln for ln in lines if ln.get("kind") == "decision"]
    assert len(dlines) == len(res.tracer.decisions)
    assert any(ln["record"]["transfers"] for ln in dlines)


def test_schema_rejects_malformed_traces():
    schema = trace_schema()
    assert validate_trace_dict({"traceEvents": []}, schema) == []
    assert validate_trace_dict({}, schema)                 # missing required
    bad_ph = {"traceEvents": [{"ph": "Z", "pid": 0, "tid": 0, "name": "x"}]}
    assert validate_trace_dict(bad_ph, schema)
    extra = {"traceEvents": [], "bogus_key": 1}
    assert validate_trace_dict(extra, schema)              # additionalProps


def test_chrome_trace_counter_tracks_are_per_machine():
    res = run(_exp())
    doc = to_chrome_trace(res.tracer)
    ctr = [e for e in doc["traceEvents"] if e["ph"] == "C"]
    names = {e["name"] for e in ctr}
    assert any(n.startswith("queue_units/m") for n in names)
    assert "units_of_work" in names and "throughput" in names


# ---------------------------------------------------------------------------
# Fused path: compile vs dispatch split without host syncs when off
# ---------------------------------------------------------------------------

def test_fused_compile_dispatch_split_jax():
    pytest.importorskip("jax")
    # 9 machines × window 7 is a shape signature unique to this test,
    # so the first run must jit-compile and the second must not
    cfg = EngineConfig(num_machines=9, cap_units=1e9, lambda_max=1357,
                       mem_queries=10**8, round_every=5, fused_window=7)

    def once():
        return run(_exp("jax", engine=cfg,
                        scenario=ScenarioSpec("uniform_normal", ticks=21,
                                              preload_queries=300,
                                              query_burst=100)))
    first = once().tracer.span_names()
    assert "fused_window_compile" in first
    assert "fused_window_dispatch" in first
    assert "fused_window" in first
    second = once().tracer.span_names()
    assert "fused_window_compile" not in second
    assert "fused_window_dispatch" in second


@pytest.mark.parametrize("plane", ["numpy", "jax"])
def test_fused_run_decisions_match_per_tick(plane):
    fused = dataclasses.replace(CFG, fused_window=8)
    dp = [(r.kind, r.decision, r.round_no,
           tuple((t.m_h, t.m_l, t.action) for t in r.transfers))
          for _, r in run(_exp(plane)).tracer.decisions]
    df = [(r.kind, r.decision, r.round_no,
           tuple((t.m_h, t.m_l, t.action) for t in r.transfers))
          for _, r in run(_exp(plane, engine=fused)).tracer.decisions]
    assert dp == df


# ---------------------------------------------------------------------------
# Fused path: host-phase spans, profiler annotations, program builds
# ---------------------------------------------------------------------------

FUSED = dataclasses.replace(CFG, fused_window=4)


def _tree(tracer) -> Counter:
    """(span, parent span) pairs of a run, counted."""
    by_seq = {e.seq: e for e in tracer.events}
    return Counter((e.name, by_seq[e.parent].name if e.parent in by_seq
                    else None) for e in tracer.events if e.kind == "span")


class _CountingAnnotation:
    opened = 0

    def __init__(self, name, **kw):
        type(self).opened += 1

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


def test_fused_run_spans_the_reindex_and_the_window_host_phases(
        monkeypatch):
    jax = pytest.importorskip("jax")
    monkeypatch.setattr(SwarmRouter, "on_round", lambda self, tick:
                        self._outcome(force_rebalance_round(self.swarm)))
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _CountingAnnotation)
    _CountingAnnotation.opened = 0
    tr = run(_exp("jax", engine=FUSED,
                  telemetry=TelemetryConfig(tick_spans=False))).tracer
    tree = _tree(tr)
    assert tree[("reindex_queries", "router_round")] >= 1
    assert tree[("collector_drain", "router_round")] >= 1
    windows = tree[("fused_window", None)]
    assert windows >= 1
    for name in ("window_stage", "state_refresh", "window_bin",
                 "window_upload", "window_readback"):
        assert tree[(name, "fused_window")] == windows, name
    reindex = [e for e in tr.events if e.name == "reindex_queries"]
    assert all(e.args["queries"] >= 400 and e.args["live"] >= M
               for e in reindex)
    # every span opened its annotation; emit_span's builds did not
    opened = sum(1 for e in tr.events
                 if e.kind == "span" and e.name != "program_build")
    assert _CountingAnnotation.opened == opened


def test_fused_run_with_telemetry_off_buffers_and_annotates_nothing(
        monkeypatch):
    jax = pytest.importorskip("jax")
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _CountingAnnotation)
    _CountingAnnotation.opened = 0
    res = run(_exp("jax", engine=FUSED, telemetry=None))
    assert res.tracer is None
    assert NOOP.events == [] and NOOP.decisions == []
    assert _CountingAnnotation.opened == 0


def test_profiler_capture_holds_the_program_spans(tmp_path):
    jax = pytest.importorskip("jax")
    with jax.profiler.trace(str(tmp_path)):
        tr = run(_exp("jax", engine=FUSED,
                      telemetry=TelemetryConfig(tick_spans=False))).tracer
    (path,) = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    host = Counter(e.name
                   for plane in jax.profiler.ProfileData.from_file(path).planes
                   if plane.name.startswith("/host:")
                   for line in plane.lines for e in line.events)
    buffered = Counter(tr.span_names())
    for name in ("fused_window", "round_close", "reindex_queries",
                 "router_round", "window_bin"):
        assert buffered[name] >= 1, name
        assert host[name] == buffered[name], name


def test_program_build_when_n_alloc_crosses_a_bucket():
    pytest.importorskip("jax")
    from repro.streaming.fused import EngineCarry, FusedParams
    from repro.streaming.planes import JaxPlane
    router = SwarmRouter(16, 4, data_plane="jax")
    h = router.fused_host_state()
    pad = (0, 128 - h.capacity)            # room for two 64-row buckets
    host = dataclasses.replace(
        h, owner=np.pad(h.owner, pad), qres=np.pad(h.qres, pad),
        area_frac=np.pad(h.area_frac, pad, constant_values=1.0))
    plane = JaxPlane()
    state = plane.make_state(host)
    # 3 ticks of 211 events: a shape no other test builds
    xy = np.random.default_rng(0).uniform(0, 1, (3, 211, 2))
    carry = EngineCarry(np.zeros(4), np.zeros(4), 211.0)

    def builds(n_alloc):
        fp = FusedParams(cap_units=1e9, lambda_max=211.0, bp_high=0.8,
                         bp_dec=0.5, bp_inc=0.1, alive=np.ones(4),
                         track_stats=True, n_alloc=n_alloc)
        tr = Tracer()
        with activate(tr):
            plane.run_window(state, router._cost_params(), fp, carry, xy)
        return ([e for e in tr.events if e.name == "program_build"],
                tr.counter_series("programs_built")[1])
    builds(host.n_alloc)                   # the first bucket's program
    (build,), (total,) = builds(65)        # the second bucket's
    assert "_window_fn" in build.args["fun"]
    assert isinstance(build.args["cached"], bool) and build.dur > 0
    assert build.parent >= 0               # under the compile span
    assert total >= 2


# ---------------------------------------------------------------------------
# ft layer: heartbeat misses, suspicion, failover
# ---------------------------------------------------------------------------

def test_heartbeat_and_failover_events():
    scen = ScenarioSpec("uniform_normal", ticks=20, preload_queries=400,
                        query_burst=150,
                        membership=(MembershipEvent(6, "fail", 2),))
    res = run(_exp(scenario=scen,
                   engine=dataclasses.replace(CFG, standby_machines=1)))
    tr = res.tracer
    names = {e.name for e in tr.events}
    assert {"heartbeat_miss", "suspect", "failure_detected",
            "membership:MachineFailure", "failover"} <= names
    suspect = next(e for e in tr.events if e.name == "suspect")
    assert suspect.track == 2 and suspect.args["silent_for"] >= 2
    recovery = [r for _, r in tr.decisions if r.kind == "recovery"]
    assert len(recovery) == 1 and recovery[0].evacuated == 2
    assert recovery[0].transfers
    assert all(t.m_h == 2 for t in recovery[0].transfers)
    assert all(c.outcome == "evacuate" for c in recovery[0].candidates)
    # the failover span wraps a plan + apply pair
    fo = next(e for e in tr.events if e.name == "failover")
    children = {e.name for e in tr.events if e.parent == fo.seq}
    assert {"plan_round", "apply_plan"} <= children


# ---------------------------------------------------------------------------
# Labels & file stems
# ---------------------------------------------------------------------------

def test_telemetry_folds_into_label_and_safe_stem():
    exp = _exp(telemetry=TelemetryConfig(trace_dir="/tmp/t"))
    assert "telemetry=telemetry(trace)" in exp.label
    stem = safe_label(exp.label)
    assert "/" not in stem and stem == stem.strip("_")
    assert os.path.basename(stem) == stem
