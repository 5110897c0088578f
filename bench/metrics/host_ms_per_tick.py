"""Engine host loop: ``fused_window`` span time less its fenced
window dispatch or compile child, summed and divided by the ticks the
windows held, in ms per tick."""


def read(r):
    spans = r.spans_named("fused_window")
    ticks = sum(int(s[3].get("ticks", 0)) for s in spans)
    if not ticks:
        return None
    return sum(r.self_ns(s) for s in spans) / ticks / 1e6
