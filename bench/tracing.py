"""Reduction of one traced run to what the per-layer readers read.

A ``--trace 1`` run keeps the program's own tracer on (host-clock spans
such as ``round_close`` and ``fused_window``) and records a
``jax.profiler`` trace of the whole measured window.  The harness puts
``bench_call`` annotations around its ``run_fused`` calls and
``bench_wait`` around its idle waits.  :class:`Readings` reads the
trace's ``.xplane.pb`` with JAX alone, aligns the program's spans to the
profiler clock through the ``bench_call`` annotations, and offers the
readers (``bench/metrics/*.py``) spans, device operations and their
union, clipped to the traced window.
"""
from __future__ import annotations

import glob
import os
import re
import time

import numpy as np

# names of the window executable (one XLA module per fused window): the
# planes jit ``functools.partial`` objects, which XLA names ``_unknown``
WINDOW_MODULE = re.compile(r"^jit__unknown|_window_fn|_sharded_window")
COLLECTIVE = re.compile(r"all-to-all|all-gather|all-reduce|"
                        r"collective-permute|reduce-scatter", re.I)
# the program's spans whose time is device work, not host work
DEVICE_SPANS = ("fused_window_dispatch", "fused_window_compile",
                "sharded_window_dispatch", "sharded_window_compile")


def start_profiler(directory: str) -> None:
    import jax
    os.makedirs(directory, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1      # the harness's annotations, no more
    opts.python_tracer_level = 0
    jax.profiler.start_trace(directory, profiler_options=opts)


def union_ns(intervals, lo: float, hi: float) -> float:
    """Length of the union of [start, end) intervals clipped to [lo, hi)."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_ns(intervals, lo: float, hi: float) -> list:
    """The idle (start, end) gaps between the union of intervals."""
    out, t = [], lo
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


class Trace:
    """Device operations and host annotations of one ``.xplane.pb``,
    in nanoseconds on the profiler clock."""

    def __init__(self, path: str):
        import jax
        pd = jax.profiler.ProfileData.from_file(path)
        self.ops: dict[str, list] = {}       # device -> [(name, s, e)]
        self.modules: dict[str, list] = {}
        self.annotations: list = []          # (name, s, e) harness spans
        for plane in pd.planes:
            if plane.name.startswith("/device:"):
                for line in plane.lines:
                    evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                           for e in line.events]
                    if line.name == "XLA Ops":
                        self.ops[plane.name] = evs
                    elif line.name == "XLA Modules":
                        self.modules[plane.name] = evs
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for e in line.events:
                        if e.name.startswith("bench_"):
                            self.annotations.append(
                                (e.name, e.start_ns,
                                 e.start_ns + e.duration_ns))
        self.annotations.sort(key=lambda a: a[1])

    @classmethod
    def from_dir(cls, directory: str) -> "Trace":
        files = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise FileNotFoundError(f"no .xplane.pb under {directory}")
        return cls(max(files, key=os.path.getmtime))


class Readings:
    """What a traced run offers the per-layer readers."""

    def __init__(self, trace: Trace, spans: list, calls: list, conf: dict,
                 peaks: dict, round_live: list):
        self.trace, self.conf, self.peaks = trace, conf, peaks
        self.round_live = round_live
        calls_prof = [a for a in trace.annotations if a[0] == "bench_call"]
        if not calls_prof or len(calls_prof) != len(calls):
            raise ValueError(f"{len(calls_prof)} bench_call annotations in "
                             f"the trace for {len(calls)} calls")
        # program spans (perf_counter ns) to the profiler clock
        self.offset = float(np.median([p[1] - c[0] for p, c in
                                       zip(calls_prof, calls)]))
        self.lo = calls_prof[0][1]
        self.hi = max(a[2] for a in trace.annotations)
        self.spans = [(name, t0 + self.offset, t0 + self.offset + dur, args,
                       seq, parent)
                      for name, t0, dur, args, seq, parent in spans]
        self.devices = sorted(trace.ops)

    @classmethod
    def from_run(cls, prof_dir: str, tracer, calls: list, conf: dict,
                 peaks: dict, round_live: list) -> "Readings":
        # the tracer's clock is perf_counter ns less its epoch
        epoch = time.perf_counter_ns() - tracer.now()
        spans = [(e.name, e.t0 + epoch, e.dur, e.args, e.seq,
                  e.parent) for e in tracer.events if e.kind == "span"]
        return cls(Trace.from_dir(prof_dir), spans, calls, conf, peaks,
                   round_live)

    # -- program spans ------------------------------------------------------
    def spans_named(self, *names) -> list:
        return [s for s in self.spans if s[0] in names
                and self.lo <= s[1] and s[2] <= self.hi]

    def self_ns(self, span) -> float:
        """A span's duration less that of its device-work children."""
        kids = sum(k[2] - k[1] for k in self.spans
                   if k[5] == span[4] and k[0] in DEVICE_SPANS)
        return (span[2] - span[1]) - kids

    # -- device -------------------------------------------------------------
    def window_s(self) -> float:
        return (self.hi - self.lo) / 1e9

    def busy_ns(self, device: str) -> float:
        return union_ns([(s, e) for _, s, e in self.trace.ops[device]],
                        self.lo, self.hi)

    def busy_s(self) -> float:
        if not self.devices:
            return 0.0
        return float(np.mean([self.busy_ns(d) for d in self.devices])) / 1e9

    def op_ns(self, pattern: re.Pattern, modules: bool = False) -> float:
        """Device time of matching operations (or modules), averaged
        over the devices, within the traced window."""
        src = self.trace.modules if modules else self.trace.ops
        if not self.devices:
            return 0.0
        return float(np.mean([
            sum(min(e, self.hi) - max(s, self.lo)
                for name, s, e in src.get(d, ())
                if pattern.search(name) and e > self.lo and s < self.hi)
            for d in self.devices]))

    def rounds_in_window(self) -> list:
        """Live partitions at each round close inside the window."""
        return [p for t, _, p in self.round_live
                if self.lo <= t + self.offset <= self.hi]

    def windows(self) -> int:
        return len(self.spans_named("fused_window"))

    def breakdown(self) -> dict:
        if not self.devices:
            return {"device_ops": [], "idle_gaps": []}
        dev = self.devices[0]
        per_op: dict[str, float] = {}
        for name, s, e in self.trace.ops[dev]:
            if e > self.lo and s < self.hi:
                per_op[name] = per_op.get(name, 0.0) + (min(e, self.hi)
                                                        - max(s, self.lo))
        ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:10]
        gaps = gaps_ns([(s, e) for _, s, e in self.trace.ops[dev]],
                       self.lo, self.hi)
        gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:10]
        return {"device_ops": [[n[:80], t / 1e9] for n, t in ops],
                "idle_gaps": [[self.doing_at((s + e) / 2), (e - s) / 1e9]
                              for s, e in gaps]}

    def doing_at(self, t: float) -> str:
        """What the host was doing at profiler time ``t``: the innermost
        program span open then, else the harness's annotation."""
        inner = None
        for name, s, e, _, _, _ in self.spans:
            if s <= t < e and (inner is None or s >= inner[1]):
                inner = (name, s)
        if inner is not None:
            return inner[0]
        for name, s, e in self.trace.annotations:
            if s <= t < e:
                return name
        return "outside"
