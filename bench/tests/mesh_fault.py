"""One run of ``lbs_range_d4`` cut to CPU size on four host devices,
optionally with the exchange between chips left out (transfers are
billed but their payload never moves between devices).  Prints the
result object.  Started by ``test_faults.py`` in a process of its own,
because the host device count is fixed when JAX starts."""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(__file__))
import conftest  # noqa: E402,F401  (paths)

import jax  # noqa: E402

import run as R  # noqa: E402
import tiny  # noqa: E402
from repro.streaming.sharded import ShardedJaxPlane  # noqa: E402

if sys.argv[1] == "exchange_left_out":
    def reshard_transfers(self, state, outcome, router):
        self.last_reshard_bytes = 0
        return 0
    ShardedJaxPlane.reshard_transfers = reshard_transfers

conf, mix = tiny.cell("lbs_range_d4", "hotspot.max")
res = R.run_cell("lbs_range_d4.hotspot.max", {"chips": 4}, conf, mix,
                 tiny.spec(), 2**31 + 77, 1.0, False, jax.devices(),
                 {"hbm_bytes_per_s": 819e9}, t_start=time.perf_counter())
print(json.dumps(res))
