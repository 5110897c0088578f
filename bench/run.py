"""Run one benchmark cell of the SWARM streaming engine on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell names a configuration (``bench/configs/<config>.json``: the
deployment, its cost model and its guarantees) and a traffic mix
(``bench/traffic/<traffic>.json``).  The run builds
``StreamingEngine(SwarmRouter(..., data_plane=...), <traffic>,
EngineConfig(...))`` and preloads the standing queries; a throwaway
engine of the same cell and seed first rehearses the window's ticks, so
that every program the window asks for is built in set-up.  After a few
warm-up rounds it drives ``StreamingEngine.run_fused`` for
``--seconds``: open loop at the mix's rate (tick k of B events is due at
t0 + (k+1)·B/rate, and every due tick is handed over, at most W per
call), or closed loop (W ticks back to back).

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1`` runs
with the program's tracer on and a profiler trace over the window, and
prints the cell's per-layer metrics, each read by its reader
``bench/metrics/<name up to the first dot>.py``.  Once the window has
closed, the plain reference (``bench/reference.py``) replays every tick
and decides ``correct``.  The last line of stdout is one JSON object;
the numbers compared, each beside its limit, are the last lines of
stderr.  Without a TPU, or with fewer chips than the cell asks for,
the run exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

import numpy as np  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)

from reference import Plan, Reference, compare, judge, simulate  # noqa: E402
from traffic.generator import Traffic  # noqa: E402

OUT = os.path.join(BENCH, "out")
# seconds past the window's close within which results of ticks that
# were due in the window may still arrive (late, not lost)
GRACE_S = 60.0
# a closed loop's rehearsal runs on until its calls that built no
# program have lasted this many times the window
CLOSED_REHEARSAL = 1.5


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def load_json(*parts) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str):
    """(cell, configuration file, mix file, BENCHMARK.json) of a cell."""
    spec = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        sys.exit(f"bench: no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    conf_entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    conf = load_json(ROOT, conf_entry["file"])
    mix = load_json(BENCH, "traffic", cell["traffic"] + ".json")
    return cell, conf, mix, spec


def require_chips(chips: int):
    """The TPU devices; exits non-zero without a TPU or enough chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"bench: JAX finds no TPU (platform {devs[0].platform!r});"
                 " refusing to run")
    if len(devs) < chips:
        sys.exit(f"bench: the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs


def peaks_for(kind: str) -> dict:
    table = load_json(BENCH, "peaks.json")
    if kind not in table:
        sys.exit(f"bench: device kind {kind!r} is not in bench/peaks.json")
    return table[kind]


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

class Recorder:
    """What the harness reads at the router's boundary for the
    reference: the plan after each round that moved partitions, the N'
    collectors each drain hands the statistics bank, and a sample of
    round closes (drawn from the seed) with the banks before and after.
    ``recording_router`` feeds it."""

    def __init__(self, close_every: int, close_phase: int):
        self.router = self.engine = None
        self.plans: dict = {}
        self.drains: list = []
        self.closes: list = []
        # (perf_counter ns, round tick, live partitions) per round
        self.round_live: list = []
        self.tick = -1
        self._close_every, self._close_phase = close_every, close_phase

    def attach(self, router, engine) -> None:
        self.router, self.engine = router, engine
        self.plans[0] = self.plan()

    def plan(self, transfers=()) -> Plan:
        p = self.router.index.parts
        live = p.live_ids()
        boxes = np.stack([p.r0[live], p.c0[live], p.r1[live], p.c1[live]], 1)
        return Plan(live, boxes, p.owner[live], transfers)

    def sample_close(self) -> bool:
        return (self.tick // max(self.engine.cfg.round_every, 1)) \
            % self._close_every == self._close_phase


def recording_router(rec: Recorder, *args, **kw):
    """A ``SwarmRouter`` whose rounds, drains and round closes ``rec``
    records; it routes, plans and closes exactly as the plain router."""
    from repro.streaming.baselines import SwarmRouter

    class RecordingRouter(SwarmRouter):
        def __init__(self, *a, **k):
            super().__init__(*a, **k)
            self.swarm.plane = _ClosePlane(self.swarm.plane, rec)

        def on_round(self, tick):
            rec.tick = tick
            rec.round_live.append((time.perf_counter_ns(), tick,
                                   len(self.index.parts.live_ids())))
            out = super().on_round(tick)
            if out.transfers:
                rec.plans[tick + 1] = rec.plan(
                    tuple((int(t.m_l), tuple(int(p) for p in t.new_pids))
                          for t in out.transfers))
            return out

        def fused_absorb(self, cn_rows, cn_cols):
            rec.drains.append((rec.engine.tick_no, cn_rows, cn_cols))
            super().fused_absorb(cn_rows, cn_cols)

    return RecordingRouter(*args, **kw)


class _ClosePlane:
    """The router's data plane, with round closes recorded when the
    recorder samples them; everything else passes through."""

    def __init__(self, plane, rec: Recorder):
        self._plane, self._rec = plane, rec

    def __getattr__(self, name):
        return getattr(self._plane, name)

    def close_round(self, stats, decay, live):
        if not self._rec.sample_close():
            return self._plane.close_round(stats, decay, live)
        live = np.asarray(live)
        before = (stats.rows[:, live].copy(), stats.cols[:, live].copy())
        self._plane.close_round(stats, decay, live)
        self._rec.closes.append((self._rec.tick, float(decay), *before,
                                 stats.rows[:, live].copy(),
                                 stats.cols[:, live].copy()))


def build(conf: dict, mix: dict, seed: int, trace: bool,
          rec: Recorder | None = None):
    """Engine, router, traffic and the standing queries of one run;
    ``rec``, when given, records what the reference needs."""
    from repro.queries import WorkloadSpec
    from repro.streaming.api import QueryBatch
    from repro.streaming import EngineConfig, TelemetryConfig
    from repro.streaming.baselines import SwarmRouter
    from repro.streaming.engine import StreamingEngine
    dep = conf["deployment"]
    keyword = dep["query_model"] == "spatial_keyword"
    workload = (WorkloadSpec(query_model="spatial_keyword",
                             term_buckets=dep["term_buckets"],
                             tuple_terms=mix["tuple_terms"],
                             sub_terms=dep["sub_terms"])
                if keyword else WorkloadSpec())
    plane = dep["plane"]
    if plane == "sharded":
        from repro.streaming.sharded import sharded_plane
        plane = sharded_plane(conf["chips"])
    # the match cost prices the area of the queries the deployment
    # registers (the router's own default is the area of its default side)
    args = (dep["grid_size"], dep["machines"])
    kw = dict(beta=dep["beta"], decay=dep["decay"], workload=workload,
              data_plane=plane, query_area=conf["cost"]["query_area"])
    router = (recording_router(rec, *args, **kw) if rec is not None
              else SwarmRouter(*args, **kw))
    check_cost_model(router, conf["cost"])
    traffic = Traffic(mix, seed, dep["batch"])
    cost = conf["cost"]
    cfg = EngineConfig(
        num_machines=dep["machines"], round_every=dep["round_every"],
        fused_window=dep["window"], lambda_max=dep["batch"],
        cap_units=dep["cap_units"],
        mem_queries=dep["mem_queries"],
        bp_high=cost["bp_high"], bp_dec=cost["bp_dec"],
        bp_inc=cost["bp_inc"],
        migration_unit_cost=cost["migration_unit_cost"],
        devices=conf["chips"] if dep["plane"] == "sharded" else 0,
        telemetry=TelemetryConfig(tick_spans=False) if trace else None)
    engine = StreamingEngine(router, traffic, cfg)
    rects = traffic.queries(dep["queries"], dep["query_side"])
    terms = (traffic.subscription_terms(dep["queries"], dep["sub_terms"])
             if keyword else None)
    router.ingest(QueryBatch(rects, 0, terms))
    if rec is not None:
        rec.attach(router, engine)
    return engine, router, traffic, rects, terms


def check_cost_model(router, cost: dict) -> None:
    """The configuration states the cost model the reference prices
    with; refuse a program whose router prices otherwise."""
    wl = router.workload
    prices = {"c0": router.c0, "kappa_probe": router.kappa_probe,
              "kappa_match": router.kappa_match, "q_cache": router.q_cache,
              "query_area": router.query_area,
              "match_factor": wl.spec.match_factor(wl.k),
              "store_cost": wl.store_cost if router.store is not None
              else 0.0,
              "delivery_cost": wl.delivery_cost if wl.spec.keyword else 0.0}
    for key, v in prices.items():
        if not math.isclose(float(v), cost[key], rel_tol=1e-12, abs_tol=0.0):
            sys.exit(f"bench: the router prices {key}={v}, "
                     f"the configuration states {cost[key]}")


BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """Programs JAX builds (compiled or read from the persistent cache)
    while the counter is open."""

    def __init__(self):
        import jax.monitoring as mon
        self.built = self.cache_hits = 0
        self.names: list = []
        self.open = False

        def on_duration(event, secs, **kw):
            if self.open and event == BACKEND_COMPILE:
                self.built += 1
                self.names.append(str(kw.get("fun_name", "?")))

        def on_event(event, **kw):
            if self.open and event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        mon.register_event_duration_secs_listener(on_duration)
        mon.register_event_listener(on_event)

    def total(self) -> int:
        return self.built + self.cache_hits

    def reset(self) -> None:
        self.built = self.cache_hits = 0
        self.names = []


# ---------------------------------------------------------------------------
# the drivers
# ---------------------------------------------------------------------------

def annotate(trace: bool, name: str):
    if not trace:
        return contextlib.nullcontext()
    import jax
    return jax.profiler.TraceAnnotation(name)


def drive_ticks(engine, window: int, ticks: int, cycle: bool = False,
                seconds: float = 0.0, counter=None) -> int:
    """At least ``ticks`` ticks through the window's own call, W per
    call, or with ``cycle`` every count from 1 to W in turn; and, with
    ``seconds``, on until the calls in which ``counter`` saw no program
    built have lasted that long.  Returns the ticks driven."""
    done, i, clean = 0, 0, 0.0
    while done < ticks or clean < seconds:
        n = (i % window) + 1 if cycle else window
        if not seconds:
            n = min(n, ticks - done)
        before = counter.total() if counter is not None else 0
        t0 = time.perf_counter()
        engine.run_fused(n, window)
        if counter is not None and counter.total() == before:
            clean += time.perf_counter() - t0
        done, i = done + n, i + 1
    return done


def rehearse(conf: dict, mix: dict, seed: int, trace: bool, window: int,
             ticks: int, seconds: float = 0.0, counter=None) -> int:
    """Build every program the window will ask for, before it opens.

    SWARM gives every partition a split creates a fresh id and never
    reuses one, so the device programs' shapes (the allocated-id prefix
    in 64-row buckets, the state's capacity, the plan patches) keep
    growing through a run.  The data planes share their compiled
    programs between engines of one process, and a cell's trajectory is
    a function of its seed and its ticks, not of the wall clock: so a
    throwaway engine of the same cell and seed, driven through the
    window's ticks in every call length from 1 to W, builds them all.
    ``seconds`` (closed loop, whose tick count the wall clock sets)
    drives it on until its calls that built nothing have lasted that
    long.  Returns the ticks rehearsed."""
    engine = build(conf, mix, seed, trace)[0]
    if counter is not None:
        counter.open = True
    done = drive_ticks(engine, window, ticks, cycle=True, seconds=seconds,
                       counter=counter)
    if counter is not None:
        counter.open = False
        counter.reset()
    del engine
    gc.collect()
    return done


def ticks_due(rate: float, batch: int, seconds: float) -> int:
    """Ticks of ``batch`` events due within ``seconds`` at ``rate``."""
    return int(math.floor(seconds * rate / batch + 1e-9))


def drive_open(engine, rate: float, batch: int, window: int, seconds: float,
               trace: bool) -> dict:
    """Open loop: every tick due is handed over, at most W per call; the
    schedule never slows.  Returns per-tick due and result times."""
    clock = time.perf_counter
    period_s = batch / rate
    n_due = ticks_due(rate, batch, seconds)
    first = engine.tick_no
    done_at = np.full(n_due, np.nan)
    late, calls = [], []
    handed = 0
    t0 = clock()
    deadline = t0 + seconds + GRACE_S
    while handed < n_due:
        now = clock()
        if now > deadline:
            break
        due = min(int(math.floor((now - t0) / period_s + 1e-9)), n_due)
        if due <= handed:
            target = t0 + (handed + 1) * period_s
            with annotate(trace, "bench_wait"):
                time.sleep(max(target - clock(), 0.0))
            late.append(clock() - target)
            continue
        n = min(due - handed, window)
        c0 = time.perf_counter_ns()
        with annotate(trace, "bench_call"):
            engine.run_fused(n, window)
        calls.append((c0, time.perf_counter_ns()))
        done_at[handed:handed + n] = clock()
        handed += n
    due_at = t0 + (np.arange(n_due) + 1) * period_s
    return {"first": first, "n_ticks": n_due, "handed": handed, "t0": t0,
            "t_end": t0 + seconds, "due_at": due_at, "done_at": done_at,
            "late_s": np.asarray(late), "calls": calls}


def drive_closed(engine, window: int, seconds: float, trace: bool) -> dict:
    """Closed loop: W ticks per call, back to back, for ``seconds``."""
    clock = time.perf_counter
    first = engine.tick_no
    done_at, calls = [], []
    t0 = clock()
    t_end = t0 + seconds
    while clock() < t_end:
        c0 = time.perf_counter_ns()
        with annotate(trace, "bench_call"):
            engine.run_fused(window, window)
        calls.append((c0, time.perf_counter_ns()))
        done_at += [clock()] * window
    n = len(done_at)
    return {"first": first, "n_ticks": n, "handed": n, "t0": t0,
            "t_end": t_end, "due_at": np.full(n, t0),
            "done_at": np.asarray(done_at), "late_s": np.zeros(0),
            "calls": calls}


# ---------------------------------------------------------------------------
# metrics
# ---------------------------------------------------------------------------

def nearest_rank(values: np.ndarray, q: float) -> float:
    """The q-th percentile by nearest rank (inf counts as a value)."""
    v = np.sort(np.asarray(values, np.float64))
    k = max(int(math.ceil(q / 100.0 * len(v))) - 1, 0)
    return float(v[k])


def end_to_end(drive: dict, injected: np.ndarray, batch: int,
               seconds: float) -> tuple[dict, int, int]:
    """Result latencies and completed events/s of one window, with the
    events attempted and failed.  A tick's events share its latency; an
    event whose result never arrived, or that backpressure shed, counts
    as beyond every limit."""
    n = drive["n_ticks"]
    lat = (drive["done_at"] - drive["due_at"]) * 1e3
    shed = batch - injected[:n]
    lat = np.where(np.isnan(lat) | (shed > 0), np.inf, lat)
    arrived = ~np.isnan(drive["done_at"])
    in_window = arrived & (drive["done_at"] <= drive["t_end"])
    completed = float(np.sum(injected[:n][in_window]))
    vals = {"events_per_s": completed / seconds}
    for q in (50, 95):
        vals[f"result_p{q}_ms"] = nearest_rank(lat, q)
    attempted = n * batch
    failed = int(np.sum(np.where(arrived, shed, batch)))
    return vals, attempted, failed


def e2e_metrics(spec: dict, cell: str) -> list:
    return [m for m in spec["end_to_end"]
            if "workloads" not in m or cell in m["workloads"]]


def layer_metrics(spec: dict, cell: str) -> list:
    return [m for m in spec["per_layer"]
            if "workloads" not in m or cell in m["workloads"]]


def reader(name: str):
    """The reader of per-layer metric ``name``:
    ``bench/metrics/<name up to the first dot>.py``, function ``read``."""
    base = name.split(".")[0]
    path = os.path.join(BENCH, "metrics", base + ".py")
    spec = importlib.util.spec_from_file_location(f"bench_metric_{base}",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def run_cell(name: str, cell: dict, conf: dict, mix: dict, spec: dict,
             seed: int, seconds: float, trace: bool, devs, peaks: dict,
             t_start: float = T_START, keep: dict | None = None) -> dict:
    """One run of one cell; returns the result object.  ``keep``, when
    given, receives the recorded run and the reference's answers (the
    control in ``bench/tests/test_control.py`` compares against them)."""
    import jax
    dep = conf["deployment"]
    window, batch = int(dep["window"]), int(dep["batch"])
    open_loop = mix["loop"] == "open"
    counter = CompileCounter()
    # rounds up to and including the mix's ``warmup_rounds``-th: SWARM
    # moves its first partitions within them
    warm = int(mix["warmup_rounds"]) * dep["round_every"] + 1
    if open_loop:
        rehearsed = rehearse(conf, mix, seed, trace, window, warm + ticks_due(
            float(mix["rate_events_per_s"]), batch, seconds))
    else:
        rehearsed = rehearse(conf, mix, seed, trace, window, warm,
                             seconds=CLOSED_REHEARSAL * seconds,
                             counter=counter)
    rec = Recorder(close_every=4, close_phase=seed % 4)
    engine, router, traffic, rects, terms = build(conf, mix, seed, trace, rec)
    # the rehearsal's engine shared the plane, and its byte counter
    resharded_before = getattr(router.plane, "reshard_bytes_total", 0)
    drive_ticks(engine, window, warm)
    prof_dir = None
    if trace:
        from tracing import start_profiler
        prof_dir = os.path.join(OUT, "trace", name)
        shutil.rmtree(prof_dir, ignore_errors=True)
        start_profiler(prof_dir)
    setup_s = time.perf_counter() - t_start
    counter.open = True
    if open_loop:
        drive = drive_open(engine, float(mix["rate_events_per_s"]), batch,
                           window, seconds, trace)
    else:
        drive = drive_closed(engine, window, seconds, trace)
    counter.open = False
    if trace:
        jax.profiler.stop_trace()
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in devs[:conf["chips"]])
    m = engine.metrics
    first, n = drive["first"], drive["n_ticks"]
    injected = np.zeros(n, np.int64)
    got = np.asarray(m.injected[first:first + drive["handed"]], np.int64)
    injected[:len(got)] = got
    vals, attempted, failed = end_to_end(drive, injected, batch, seconds)
    late_ms = drive["late_s"] * 1e3 if len(drive["late_s"]) else np.zeros(1)
    # a backlog that grows over the window shows as a later quarter of
    # ticks waiting longer than the first
    lat_ms = (drive["done_at"] - drive["due_at"]) * 1e3
    q = max(n // 4, 1)
    call_ms = sorted((b - a) / 1e6 for a, b in drive["calls"])
    log(f"[{name}] seed={seed} ticks_due={n} handed={drive['handed']} "
        f"calls={len(call_ms)} call_ms p50={np.median(call_ms):.2f} "
        f"slowest={[round(c, 1) for c in call_ms[-3:]]} "
        f"setup_s={setup_s:.3f} rehearsed_ticks={rehearsed} "
        f"compiled_in_window={counter.built} "
        f"(persistent-cache hits {counter.cache_hits}: "
        f"{','.join(counter.names)}) "
        f"generator_late_ms p50={np.median(late_ms):.3f} "
        f"result_ms first_quarter_p50={np.median(lat_ms[:q]):.3f} "
        f"last_quarter_p50={np.median(lat_ms[-q:]):.3f} "
        f"max={late_ms.max():.3f} "
        f"partitions={router.index.parts.n_alloc} "
        f"rounds={len(rec.round_live)} plans={len(rec.plans)}")
    result = {"correct": False, "attempted": int(attempted),
              "failed": int(failed), "metrics": {}}
    if trace:
        from tracing import Readings
        rd = Readings.from_run(prof_dir, engine.tracer, drive["calls"], conf,
                               peaks, rec.round_live)
        for mt in layer_metrics(spec, name):
            v = reader(mt["name"])(rd)
            if v is not None:
                result["metrics"][mt["name"]] = {"value": v,
                                                 "unit": mt["unit"]}
        busy, span = rd.busy_s(), rd.window_s()
        result["breakdown"] = rd.breakdown()
    else:
        vals["setup_s"] = setup_s
        for mt in e2e_metrics(spec, name):
            result["metrics"][mt["name"]] = {"value": vals[mt["name"]],
                                             "unit": mt["unit"]}
    result["device"] = {"platform": devs[0].platform,
                        "kind": devs[0].device_kind, "count": len(devs),
                        "memory_peak_bytes": int(mem)}
    if trace:
        result["device"].update(busy_s=busy, window_s=span)
    # the reference runs once the window has closed and memory is read
    hi = first + drive["handed"]
    run = {"ticks": hi, "window_ticks": (first, hi),
           "points": traffic.points,
           "terms": traffic.terms if terms is not None else None,
           "plans": rec.plans, "drains": rec.drains, "closes": rec.closes,
           "outputs": {"injected": m.injected, "utilization": m.utilization,
                       "deliveries": m.deliveries}}
    reference = (dep, conf["cost"], rects, terms)
    want = simulate(run, Reference(*reference))
    numbers = compare(run, want)
    if keep is not None:
        keep.update(run=run, want=want, reference=reference, drive=drive)
    # nothing may be built inside the window: a compile, or a program
    # read from the persistent cache, there is not the system's steady work
    numbers["programs_built_in_window"] = float(counter.total())
    if dep["plane"] == "sharded":
        billed = int(sum(m.migration_bytes))
        numbers["reshard_bytes_off"] = float(abs(
            router.plane.reshard_bytes_total - resharded_before - billed))
    ok, checks = judge(numbers, conf["limits"])
    result["correct"] = bool(ok and drive["handed"] == n)
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell, conf, mix, spec = load_cell(args.workload)
    devs = require_chips(int(cell["chips"]))
    peaks = peaks_for(devs[0].device_kind)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import jax
    from repro.launch.mesh import use_compile_cache
    use_compile_cache()
    # every program of the cell, however quick to compile, is kept
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    result = run_cell(args.workload, cell, conf, mix, spec, args.seed,
                      args.seconds, bool(args.trace), devs, peaks)
    for k, c in result["checks"].items():
        log(f"check {k} = {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
