"""Engine host loop: host-device transfer time per fused window, in ms:
``window_upload`` (the histograms, the carry and the scalars to the
device) plus ``window_readback`` (the carry and the per-tick outputs
back), summed over the window and divided by the ``fused_window``
spans.  A traced run fences the window's dispatch with
``block_until_ready`` first, so the readback here is a copy, not a
wait for the device."""


def read(r):
    n = r.windows()
    spans = r.spans_named("window_upload", "window_readback")
    if not n or not spans:
        return None
    return sum(s[2] - s[1] for s in spans) / n / 1e6
