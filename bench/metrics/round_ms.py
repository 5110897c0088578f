"""Round path: mean ``round_close`` span per round (it encloses the
round close, ``plan_round`` and ``apply_plan``), in ms."""
import numpy as np


def read(r):
    spans = r.spans_named("round_close")
    if not spans:
        return None
    return float(np.mean([s[2] - s[1] for s in spans])) / 1e6
