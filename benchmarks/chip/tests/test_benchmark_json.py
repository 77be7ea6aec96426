"""`BENCHMARK.json` against the contract it is written to, and every
name in it against a file that exists."""
import json
import math
import re

import pytest

import harness

BENCH = harness.load_json(harness.ROOT / "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
METRIC_KEYS = {"name", "unit", "better", "source"}


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert all(PATH.match(p) and ".." not in p for p in BENCH["paths"])
    assert all(not w.startswith("/") and ".." not in w
               for w in BENCH["command"])
    assert len(json.dumps(BENCH)) <= 64 * 1024


def test_run_seconds_fit_a_full_check_of_24_cells():
    rs = BENCH["run_seconds"]
    assert 1 <= rs <= 51
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200


def _one_line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


@pytest.mark.parametrize("cfg", BENCH["configs"], ids=lambda c: c["name"])
def test_config_file_holds_what_is_run(cfg):
    assert set(cfg) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(cfg["name"]) and _one_line(cfg["why"])
    assert cfg["file"].startswith(BENCH["paths"][0] + "/")
    data = harness.load_json(harness.ROOT / cfg["file"])
    assert data["name"] == cfg["name"] and data["source"] == cfg["source"]
    assert sorted(data["reduced"]) == sorted(cfg["reduced"])
    for key, cut in data["reduced"].items():
        assert NAME.match(key) and data[key] == cut["run"]
    assert sum(math.prod(x["shape"]) for x in data["leaves"]) \
        == data["params"]
    assert any(w["config"] == cfg["name"] for w in BENCH["workloads"])


@pytest.mark.parametrize("wl", BENCH["workloads"], ids=lambda w: w["name"])
def test_workload_names_files_that_exist(wl):
    assert set(wl) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(wl["name"]) and NAME.match(wl["traffic"])
    assert wl["chips"] in (1, 4) and _one_line(wl["why"])
    cell = harness.load_cell(wl["name"], BENCH)
    limits = cell["traffic"]["limits"]
    assert all(isinstance(v, (int, float)) for v in limits.values())
    for m in (harness.metrics_for(BENCH, wl["name"], False)
              + harness.metrics_for(BENCH, wl["name"], True)):
        assert callable(harness.load_reader(m["name"]))
    assert {"setup_s"} < {m["name"] for m in
                          harness.metrics_for(BENCH, wl["name"], False)}
    assert harness.metrics_for(BENCH, wl["name"], True)


def test_metrics():
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    cells = {w["name"] for w in BENCH["workloads"]}
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == METRIC_KEYS | {"bound"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    assert {m["name"]: m["bound"]
            for m in BENCH["end_to_end"]}["setup_s"] <= 0.25
    layers = {}
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == METRIC_KEYS | {"layer", "moves"}
        assert m["moves"] in e2e and _one_line(m["layer"])
        assert set(m.get("workloads", [])) <= cells
        layers.setdefault(m["layer"], []).append(m["name"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
    perf = (harness.ROOT / "PERF.md").read_text()
    assert all(f"`{layer}`" in perf for layer in layers)
