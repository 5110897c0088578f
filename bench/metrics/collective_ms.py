"""Mesh: device time of the collective operations (all-to-all and the
like) per fused window, from the profiler trace, in ms (mean over the
chips)."""
from tracing import COLLECTIVE


def read(r):
    n = r.windows()
    t = r.op_ns(COLLECTIVE)
    if not n or t <= 0:
        return None
    return t / n / 1e6
