"""The program's spans on the profiler's clock: every span the tracer
buffers, moved by ``Readings``' median ``bench_call`` offset, lies on
its copy in the capture's ``.xplane.pb``.  A short closed-loop run of
``lbs_range.hotspot.max`` cut to CPU size."""
import jax
import numpy as np

import run as R
import tiny
from tracing import Readings, start_profiler

SEED = 2**31 + 91
TOLERANCE_MS = 0.5


def host_events(path: str, names: set) -> dict:
    """Profiler intervals of the host events named in ``names``."""
    out: dict[str, list] = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in names:
                        out.setdefault(e.name, []).append(
                            (e.start_ns, e.start_ns + e.duration_ns))
    return out


def test_buffered_spans_lie_on_their_profiler_copies(tmp_path):
    conf, mix = tiny.cell("lbs_range", "hotspot.max")
    window = conf["deployment"]["window"]
    rec = R.Recorder(close_every=4, close_phase=0)
    engine = R.build(conf, mix, SEED, True, rec)[0]
    R.drive_ticks(engine, window, 3 * window)
    start_profiler(str(tmp_path))
    drive = R.drive_closed(engine, window, 1.0, True)
    jax.profiler.stop_trace()
    r = Readings.from_run(str(tmp_path), engine.tracer, drive["calls"], conf,
                          {}, rec.round_live)
    # program builds are recorded after the fact, buffer only
    spans = [s for s in r.spans if s[0] != "program_build"
             and r.lo <= s[1] and s[2] <= r.hi]
    names = {s[0] for s in spans}
    assert {"fused_window", "window_bin", "router_round",
            "reindex_queries"} <= names
    (path,) = list(tmp_path.glob("**/*.xplane.pb"))
    copies = host_events(str(path), names)
    worst = 0.0
    for name in names:
        mine = np.array(sorted((s[1], s[2]) for s in spans if s[0] == name))
        theirs = np.array(sorted(copies.get(name, ())))
        assert mine.shape == theirs.shape, name
        worst = max(worst, float(np.abs(mine - theirs).max()) / 1e6)
    print(f"largest offset of a buffered span from its copy: {worst:.4f} ms "
          f"({len(spans)} spans)")
    assert worst < TOLERANCE_MS
