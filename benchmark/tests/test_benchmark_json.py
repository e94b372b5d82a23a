"""`BENCHMARK.json` against the contract's limits that can be checked
without a run, and every name in it against the files under `benchmark/`."""

import json
import os
import re

import pytest

from benchmark.harness import BENCH_DIR, ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(_dim|_rank)$")


@pytest.fixture(scope="module")
def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        raw = f.read()
    assert len(raw.encode()) <= 64 * 1024
    return json.loads(raw)


def _line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_command(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["benchmark"]
    assert 1 <= len(bench["command"]) <= 32
    assert all(_line(w) and not w.startswith("/") and ".." not in w
               for w in bench["command"])
    assert bench["command"][1] == "benchmark/run.py"
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51


def test_configs_name_their_files(bench):
    files = set()
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"])
        assert _line(c["why"])
        assert PATH.match(c["file"])
        assert c["file"] == f"benchmark/configs/{c['name']}.json"
        assert c["file"] not in files
        files.add(c["file"])
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key) and not WIDTH.search(key)
            assert key in cfg and key in cfg["reduced"]
        assert cfg["source"] == c["source"]
        fixture = os.path.join(BENCH_DIR, "fixtures", cfg["fixture"]["file"])
        assert os.path.exists(fixture)
        assert cfg["env"]["DRAND_TPU_BUCKETS"] == str(cfg["bucket_rounds"])
        assert cfg["guarantees"]
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}


def test_workloads_name_a_config_a_traffic_file_and_a_driver(bench):
    assert 1 <= len(bench["workloads"]) <= 24
    pairs, names = set(), set()
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        assert w["name"] not in names
        names.add(w["name"])
        path = os.path.join(BENCH_DIR, "traffic", w["traffic"] + ".json")
        with open(path) as f:
            traffic = json.load(f)
        assert os.path.exists(os.path.join(
            BENCH_DIR, "drivers", traffic["driver"] + ".py"))
    four = sum(1 for w in bench["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(bench["workloads"]) // 2)


def test_metrics(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert len(e2e) == len(bench["end_to_end"]) <= 16
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m.get("workloads", cells)) <= cells
    layers = set()
    assert 1 <= len(bench["per_layer"]) <= 128
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["name"] not in layers and m["name"] not in e2e
        layers.add(m["name"])
        assert m["source"] in SOURCES and _line(m["layer"])
        moved = e2e[m["moves"]]
        assert set(m.get("workloads", cells)) <= set(
            moved.get("workloads", cells))
        with open(os.path.join(BENCH_DIR, "layer_metrics",
                               m["name"] + ".json")) as f:
            spec = json.load(f)
        assert spec["kind"] in ("build", "stats", "span", "reader")
        if spec["kind"] == "reader":
            assert os.path.exists(os.path.join(
                BENCH_DIR, "readers", spec["reader"] + ".py"))
    for cell in cells:
        mine = [m for m in bench["end_to_end"]
                if cell in m.get("workloads", cells)]
        assert len(mine) >= 2, f"{cell} reports setup_s and one more"
        assert any(cell in m.get("workloads", cells)
                   for m in bench["per_layer"])


def test_files_under_benchmark_are_named_from_permitted_characters():
    for folder, _dirs, files in os.walk(BENCH_DIR):
        if "__pycache__" in folder:
            continue
        for name in files:
            rel = os.path.relpath(os.path.join(folder, name), ROOT)
            assert PATH.match(rel), rel
