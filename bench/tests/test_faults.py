"""The comparison that decides ``correct`` catches each fault the cells
can have.  Each test drives a whole run of a cell cut to CPU size
(``tiny``), past the harness's look for a chip, with the timed path
broken underneath, and sees ``correct`` come out false; an unbroken run
comes out true."""
import json
import os
import subprocess
import sys
import time

import jax
import numpy as np
import pytest

import run as R
import tiny
from repro.streaming.planes import JaxPlane

SEED = 2**31 + 77
PEAKS = {"hbm_bytes_per_s": 819e9}


def run_tiny(config: str, traffic: str, seconds: float = 1.0) -> dict:
    conf, mix = tiny.cell(config, traffic)
    return R.run_cell(f"{config}.{traffic}", {"chips": conf["chips"]}, conf,
                      mix, tiny.spec(), SEED, seconds, False, jax.devices(),
                      PEAKS, t_start=time.perf_counter())


def broken(monkeypatch, fault):
    """Wrap ``JaxPlane.run_window`` so that ``fault`` rewrites its
    arguments or its answer."""
    orig = JaxPlane.run_window

    def run_window(self, state, cp, fp, carry, xy_stack, kw_stack=None,
                   cells=None):
        return fault(orig, self, state, cp, fp, carry, xy_stack, kw_stack,
                     cells)
    monkeypatch.setattr(JaxPlane, "run_window", run_window)


CELLS = [("lbs_range", "hotspot.rate"), ("geo_pubsub", "hashtags.max")]


@pytest.mark.parametrize("config,traffic", CELLS)
def test_sound_run_is_correct(config, traffic):
    res = run_tiny(config, traffic)
    assert res["correct"], res["checks"]


@pytest.mark.parametrize("config,traffic", CELLS)
def test_answer_altered_where_produced(monkeypatch, config, traffic):
    def fault(orig, self, *args):
        state, carry, outs, ok = orig(self, *args)
        util = outs.utilization.copy()
        util[0] *= 1.01
        dels = None if outs.deliveries is None else outs.deliveries * 1.01
        return state, carry, outs._replace(utilization=util,
                                           deliveries=dels), ok
    broken(monkeypatch, fault)
    res = run_tiny(config, traffic)
    assert not res["correct"]
    assert res["checks"]["utilization_rel_err"]["value"] > \
        res["checks"]["utilization_rel_err"]["limit"]


@pytest.mark.parametrize("config,traffic", CELLS)
def test_half_of_the_batch_left_out(monkeypatch, config, traffic):
    def fault(orig, self, state, cp, fp, carry, xy, kw, cells):
        half = xy.shape[1] // 2
        return orig(self, state, cp, fp, carry, xy[:, :half],
                    None if kw is None else kw[:, :half], cells)
    broken(monkeypatch, fault)
    res = run_tiny(config, traffic)
    assert not res["correct"]
    assert res["checks"]["collectors_max_diff"]["value"] > 0


@pytest.mark.parametrize("config,traffic", CELLS)
def test_state_returned_unchanged(monkeypatch, config, traffic):
    def fault(orig, self, state, *args):
        _, carry, outs, ok = orig(self, state, *args)
        return state, carry, outs, ok
    broken(monkeypatch, fault)
    res = run_tiny(config, traffic)
    assert not res["correct"]
    assert res["checks"]["collectors_max_diff"]["value"] > 0


def test_round_close_altered(monkeypatch):
    orig = JaxPlane.close_round

    def close_round(self, stats, decay, live):
        orig(self, stats, decay, live)
        stats.rows[0, np.asarray(live)[:1], 3] += 1.0
    monkeypatch.setattr(JaxPlane, "close_round", close_round)
    res = run_tiny("lbs_range", "hotspot.rate", seconds=3.0)
    assert not res["correct"]
    assert res["checks"]["close_max_diff"]["value"] > 0


MESH_SCRIPT = os.path.join(os.path.dirname(__file__), "mesh_fault.py")


@pytest.mark.parametrize("fault", ["none", "exchange_left_out"])
def test_mesh_exchange_left_out(fault):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    out = subprocess.run([sys.executable, MESH_SCRIPT, fault], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["correct"] == (fault == "none"), res["checks"]
    if fault != "none":
        assert res["checks"]["reshard_bytes_off"]["value"] > 0



UNREHEARSED = os.path.join(os.path.dirname(__file__), "unrehearsed.py")


def test_program_built_inside_the_window():
    """Without the rehearsal the window builds the shapes that its
    calls and the partition ids it allocates ask for, and the run is
    refused.  In a process of its own: the planes keep their programs
    for the life of a process."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, UNREHEARSED], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert not res["correct"]
    assert res["checks"]["programs_built_in_window"]["value"] > 0
