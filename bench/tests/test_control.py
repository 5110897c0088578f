"""The control: the plain reference put in the program's place and
computed one precision step below the configuration's.  The
configurations state float32 work units and delivery terms, and exact
counts from float32 contractions at ``Precision.HIGHEST``.  The control
runs the reference's contractions (counts and work units by owner, the
N' collectors, the keyword deliveries and the Algorithm-2 prefix sums)
as float32 ``einsum`` at ``Precision.HIGH``, three bf16 passes, on the
chip, and stores the per-partition work and delivery terms in
bfloat16.  Its answers, judged against the float64 reference over the
same events and plans, must come out not correct, while the program's
own answers from the same run come out correct.

On the chip, by hand (a TPU is required; the test skips without one):

    python3 -m pytest -q bench/tests/test_control.py

or, to print the readings of chosen seeds for one cell:

    python3 bench/tests/test_control.py <cell> <seconds> <seed> [<seed>...]
"""
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(__file__))
import conftest  # noqa: E402,F401  (paths)

import numpy as np  # noqa: E402
import pytest  # noqa: E402

import run as R  # noqa: E402
from reference import Reference, as_run, compare, judge, simulate  # noqa: E402

SEEDS = (2**31 + 11, 2**31 + 12, 2**31 + 13)
# the cells' own window
SECONDS = float(R.load_json(R.ROOT, "BENCHMARK.json")["run_seconds"])


def contract_high(subscripts, *ops):
    import jax
    import jax.numpy as jnp
    out = jnp.einsum(subscripts, *(jnp.asarray(o, jnp.float32) for o in ops),
                     precision=jax.lax.Precision.HIGH)
    return np.asarray(out, np.float64)


def to_bf16(a):
    import ml_dtypes
    return np.asarray(a, np.float64).astype(ml_dtypes.bfloat16) \
        .astype(np.float64)


def readings(cell_name: str, seconds: float, seeds) -> list:
    """(seed, program numbers, control numbers, limits) per seed."""
    cell, conf, mix, spec = R.load_cell(cell_name)
    devs = R.require_chips(int(cell["chips"]))
    peaks = R.peaks_for(devs[0].device_kind)
    import jax
    from repro.launch.mesh import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    out = []
    for seed in seeds:
        keep = {}
        res = R.run_cell(cell_name, cell, conf, mix, spec, seed, seconds,
                         False, devs, peaks, t_start=time.perf_counter(),
                         keep=keep)
        prog = {k: c["value"] for k, c in res["checks"].items()}
        ctrl_answers = simulate(keep["run"], Reference(*keep["reference"],
                                                       contract=contract_high,
                                                       rounding=to_bf16))
        ctrl = compare(as_run(keep["run"], ctrl_answers), keep["want"])
        out.append((seed, prog, ctrl, conf["limits"]))
    return out


def test_control_fails_and_program_passes():
    import jax
    if jax.devices()[0].platform != "tpu":
        pytest.skip("the control runs on the chip")
    spec = R.load_json(R.ROOT, "BENCHMARK.json")
    for w in spec["workloads"]:
        if len(jax.devices()) < w["chips"]:
            continue
        for seed, prog, ctrl, limits in readings(w["name"], SECONDS, SEEDS):
            assert judge(prog, limits)[0], (w["name"], seed, prog)
            assert not judge(ctrl, limits)[0], (w["name"], seed, ctrl)


if __name__ == "__main__":
    name, seconds = sys.argv[1], float(sys.argv[2])
    for seed, prog, ctrl, limits in readings(
            name, seconds, [int(s) for s in sys.argv[3:]]):
        print(json.dumps({"cell": name, "seed": seed, "program": prog,
                          "control": ctrl,
                          "program_correct": judge(prog, limits)[0],
                          "control_correct": judge(ctrl, limits)[0]}),
              flush=True)
