"""One run of ``lbs_range.hotspot.rate`` cut to CPU size with the
rehearsal left out of set-up.  Prints the result object.  Started by
``test_faults.py`` in a process of its own, so that no program is
built before the run."""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(__file__))
import conftest  # noqa: E402,F401  (paths)

import jax  # noqa: E402

import run as R  # noqa: E402
import tiny  # noqa: E402

R.rehearse = lambda *a, **k: 0
conf, mix = tiny.cell("lbs_range", "hotspot.rate")
res = R.run_cell("lbs_range.hotspot.rate", {"chips": 1}, conf, mix,
                 tiny.spec(), 2**31 + 77, 8.0, False, jax.devices(),
                 {"hbm_bytes_per_s": 819e9}, t_start=time.perf_counter())
print(json.dumps(res))
