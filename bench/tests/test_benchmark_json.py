"""``BENCHMARK.json`` keeps to the benchmark's contract: names, units,
keys, files the harness finds by name, and what each cell reports."""
import json
import os
import re

import tiny

ROOT = os.path.dirname(tiny.BENCH)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def spec():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_keys_and_sizes():
    s = spec()
    assert set(s) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10
    assert 1 <= len(s["command"]) <= 32 and all(line(w) for w in s["command"])
    assert 1 <= len(s["paths"]) <= 16
    for p in s["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p)
        assert not p.startswith("/") and ".." not in p.split("/")
    assert isinstance(s["run_seconds"], int) and 1 <= s["run_seconds"] <= 51
    # a full check of 24 cells fits its time
    cells = 24
    assert (2 + 14 * cells) * (s["run_seconds"] + 60) \
        + cells * 2 * 90 + 1200 <= 43200


def test_names_units_and_entry_keys():
    s = spec()
    names = [c["name"] for c in s["configs"]] + \
        [w["name"] for w in s["workloads"]] + \
        [m["name"] for m in s["end_to_end"] + s["per_layer"]]
    assert all(NAME.match(n) for n in names)
    for group in ("configs", "workloads"):
        assert len({e["name"] for e in s[group]}) == len(s[group])
    metrics = s["end_to_end"] + s["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for c in s["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert line(c["source"]) and line(c["why"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
    for w in s["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and line(w["why"])
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    for m in metrics:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in s["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in s["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert line(m["layer"])


def test_files_found_by_name():
    s = spec()
    bench = s["paths"][0]
    for c in s["configs"]:
        assert c["file"].startswith(bench + "/")
        with open(os.path.join(ROOT, c["file"])) as f:
            conf = json.load(f)
        assert conf["name"] == c["name"]
        for key in c["reduced"]:
            assert key in conf["deployment"] or key in conf["assumed"]
    assert len({c["file"] for c in s["configs"]}) == len(s["configs"])
    for w in s["workloads"]:
        assert os.path.exists(os.path.join(ROOT, bench, "traffic",
                                           w["traffic"] + ".json"))
    for m in s["per_layer"]:
        base = m["name"].split(".")[0]
        assert os.path.exists(os.path.join(ROOT, bench, "metrics",
                                           base + ".py"))


def test_every_cell_reports_what_the_contract_asks():
    s = spec()
    e2e = {m["name"]: m for m in s["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    cells = {w["name"] for w in s["workloads"]}
    four = sum(w["chips"] == 4 for w in s["workloads"])
    assert four <= max(1, len(cells) // 2)

    def reports(metric, cell):
        return "workloads" not in metric or cell in metric["workloads"]
    for cell in cells:
        assert sum(reports(m, cell) for m in s["end_to_end"]) >= 2
        assert any(reports(m, cell) for m in s["per_layer"])
    for m in s["per_layer"]:
        assert m["moves"] in e2e
        for cell in m.get("workloads", ()):
            assert cell in cells and reports(e2e[m["moves"]], cell)
    layers = {}
    for m in s["per_layer"]:
        layers.setdefault(m["name"].split(".")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())
