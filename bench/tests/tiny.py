"""Cells of the benchmark cut to a size the CPU runs in seconds, for the
self-tests.  Only scale changes: the grid, the queries, the batch and
the capacity that keeps the busiest machine below saturation."""
import copy
import json
import os

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def load(*parts):
    with open(os.path.join(BENCH, *parts)) as f:
        return json.load(f)


def cell(config: str, traffic: str):
    conf = copy.deepcopy(load("configs", config + ".json"))
    mix = copy.deepcopy(load("traffic", traffic + ".json"))
    dep = conf["deployment"]
    if dep["query_model"] == "spatial_keyword":
        dep.update(grid_size=32, queries=20_000, batch=2_000,
                   cap_units=64.0 * 20_000)
    else:
        dep.update(grid_size=32, queries=5_000, batch=8_192,
                   cap_units=300.0 * 8_192)
    if mix["loop"] == "open":
        mix["rate_events_per_s"] = 2e5
    return conf, mix


def spec():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        return json.load(f)
