"""Engine host loop: ``window_bin`` time (the host pre-pass that bins
each tick's points into the per-tick cell histograms) summed over the
window and divided by the ticks the ``fused_window`` spans held, in ms
per tick."""


def read(r):
    windows = r.spans_named("fused_window")
    ticks = sum(int(s[3].get("ticks", 0)) for s in windows)
    spans = r.spans_named("window_bin")
    if not ticks or not spans:
        return None
    return sum(s[2] - s[1] for s in spans) / ticks / 1e6
