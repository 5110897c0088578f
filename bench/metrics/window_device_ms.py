"""Window program: device time of the window executable per fused
window, from the profiler trace, in ms (mean over the chips)."""
from tracing import WINDOW_MODULE


def read(r):
    n = r.windows()
    t = r.op_ns(WINDOW_MODULE, modules=True)
    if not n or t <= 0:
        return None
    return t / n / 1e6
