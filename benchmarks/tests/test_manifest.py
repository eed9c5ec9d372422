"""Lint of ``BENCHMARK.json`` and the files it names, to the contract the
driver checks before any run, and to what the harness needs to resolve every
cell and metric by name."""

import importlib
import os
import re

import pytest

from conftest import BENCH, ROOT, load

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def manifest():
    import json
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_shape():
    m = manifest()
    assert set(m) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10
    assert isinstance(m["run_seconds"], int) and 1 <= m["run_seconds"] <= 51
    assert 1 <= len(m["command"]) <= 32 and all(_line(w) for w in m["command"])
    assert 1 <= len(m["paths"]) <= 16
    for p in m["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    # the command names no file of the repo outside paths
    for word in m["command"]:
        if os.path.exists(os.path.join(ROOT, word)):
            assert any(word.startswith(p + "/") for p in m["paths"])
    # every file under paths is named from a name's characters
    for p in m["paths"]:
        for d, dirs, files in os.walk(os.path.join(ROOT, p)):
            dirs[:] = [x for x in dirs if x not in ("out", "__pycache__")]
            for fn in files:
                rel = os.path.relpath(os.path.join(d, fn), ROOT)
                assert PATH.match(rel), rel


def test_configs():
    m = manifest()
    assert 1 <= len(m["configs"]) <= 24
    names = [c["name"] for c in m["configs"]]
    files = [c["file"] for c in m["configs"]]
    assert len(set(names)) == len(names) and len(set(files)) == len(files)
    used = {w["config"] for w in m["workloads"]}
    for c in m["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert _line(c["source"]) and _line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in m["paths"])
        assert len(c["reduced"]) <= 16
        assert all(NAME.match(k) for k in c["reduced"])
        body = load(*c["file"].split("/")[1:])
        assert body["source"] == c["source"]
        assert body["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert key in body and key in body["reduced_why"]
            assert not re.search(r"(_dim|_rank)$", key)
        assert body["assumed"] and body["guarantees"]
        # the configuration's builder and its plain reference are beside it
        stem = c["file"][:-len(".json")]
        assert os.path.isfile(os.path.join(ROOT, stem + ".py"))
        assert os.path.isfile(os.path.join(ROOT, stem + "_oracle.py"))


def test_workloads():
    m = manifest()
    assert 1 <= len(m["workloads"]) <= 24
    names = [w["name"] for w in m["workloads"]]
    pairs = [(w["config"], w["traffic"]) for w in m["workloads"]]
    assert len(set(names)) == len(names) and len(set(pairs)) == len(pairs)
    configs = {c["name"] for c in m["configs"]}
    four = sum(w["chips"] == 4 for w in m["workloads"])
    assert four <= max(len(names) // 2, 1)
    for w in m["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert all(NAME.match(w[k]) for k in ("name", "config", "traffic"))
        assert w["config"] in configs and w["chips"] in (1, 4)
        assert _line(w["why"])
        cell = load("workloads", f"{w['name']}.json")
        for k in ("name", "config", "traffic", "chips", "why"):
            assert cell[k] == w[k], (w["name"], k)
        mix = load("traffic", f"{w['traffic']}.json")
        assert mix["loop"] in ("closed", "open")
        assert (mix["loop"] == "open") == bool(cell.get("rate"))
        assert cell["chunk"] > 0 and cell["warmup"]["seconds"] > 0


def _cells_of(metric, all_cells):
    return metric.get("workloads", all_cells)


def test_metrics():
    m = manifest()
    cells = [w["name"] for w in m["workloads"]]
    e2e = {x["name"]: x for x in m["end_to_end"]}
    assert 1 <= len(e2e) <= 16 and 1 <= len(m["per_layer"]) <= 128
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    every = [x["name"] for x in m["end_to_end"] + m["per_layer"]]
    assert len(set(every)) == len(every)
    for x in m["end_to_end"]:
        assert set(x) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert x["source"] in ("host_clock", "device_trace")
        assert 0.01 <= x["bound"] <= 0.25
        spec = load("e2e_metrics", f"{x['name']}.json")
        for k in ("name", "unit", "better", "source"):
            assert spec[k] == x[k], (x["name"], k)
        importlib.import_module(f"e2e_metrics.readers.{spec['reader']}")
    layers = set()
    for x in m["per_layer"]:
        assert set(x) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert _line(x["layer"])
        layers.add(x["layer"])
        spec = load("layer_metrics", f"{x['name']}.json")
        for k in ("name", "unit", "better", "source", "layer", "moves"):
            assert spec[k] == x[k], (x["name"], k)
        importlib.import_module(f"layer_metrics.readers.{spec['reader']}")
        # moves names an end-to-end metric that every listed cell reports
        assert x["moves"] in e2e and x["moves"] != "setup_s"
        for cell in _cells_of(x, cells):
            assert cell in cells
            assert cell in _cells_of(e2e[x["moves"]], cells), (x["name"], cell)
    for x in m["end_to_end"] + m["per_layer"]:
        assert NAME.match(x["name"]) and UNIT.match(x["unit"])
        assert x["better"] in ("lower", "higher")
        assert x["source"] in SOURCES
        if "roofline" in x["name"] or "mfu" in x["name"]:
            assert x["unit"] == "%"
    # every cell reports setup_s, another end-to-end metric and a layer metric
    for cell in cells:
        assert sum(cell in _cells_of(x, cells) for x in m["end_to_end"]) >= 2
        assert any(cell in _cells_of(x, cells) for x in m["per_layer"])
    # the layers are those PERF.md lists
    with open(os.path.join(ROOT, "PERF.md")) as f:
        perf = f.read()
    for layer in layers:
        assert f"| {layer} " in perf or f"| {layer} (" in perf, layer


def test_names_stay_in_their_own_files():
    """run.py and the harness hold no cell's, configuration's or metric's
    name; a per-layer metric's name stands only in its own file and the
    manifest; what generates load or decides ``correct`` imports nothing of
    the program."""
    m = manifest()
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in m[k]]
    generic = [os.path.join(BENCH, "run.py")] + [
        os.path.join(BENCH, "harness", fn)
        for fn in os.listdir(os.path.join(BENCH, "harness"))
        if fn.endswith(".py")]
    for path in generic:
        with open(path) as f:
            text = f.read()
        for name in names:
            assert not re.search(rf"(?<![\w.]){re.escape(name)}(?![\w.])",
                                 text), (path, name)
    for x in m["per_layer"]:
        own = os.path.join(BENCH, "layer_metrics", f"{x['name']}.json")
        for d, dirs, files in os.walk(BENCH):
            dirs[:] = [y for y in dirs if y not in ("out", "__pycache__",
                                                    "tests")]
            for fn in files:
                path = os.path.join(d, fn)
                if path == own or not fn.endswith((".py", ".json")):
                    continue
                with open(path) as f:
                    assert x["name"] not in f.read(), (path, x["name"])
    independent = [os.path.join(BENCH, "harness", fn) for fn in
                   ("generator.py", "check.py", "bytes_model.py",
                    "trace_reduce.py", "peaks.py")]
    independent += [os.path.join(BENCH, "configs", fn)
                    for fn in os.listdir(os.path.join(BENCH, "configs"))
                    if fn.endswith("_oracle.py")]
    for path in independent:
        with open(path) as f:
            assert "windflow_tpu" not in f.read().replace(
                "src/windflow_tpu", ""), path


def test_cells_of_one_configuration_differ_in_traffic_only():
    m = manifest()
    by_cfg = {}
    for w in m["workloads"]:
        by_cfg.setdefault(w["config"], []).append(
            load("workloads", f"{w['name']}.json"))
    for cells in by_cfg.values():
        for c in cells:
            assert set(c) <= {"name", "config", "traffic", "chips", "chunk",
                              "rate", "sweep", "why", "warmup", "rehearsal"}


@pytest.mark.parametrize("bad", ["a b", "a,b", "a/b", "", "-x", "x" * 65,
                                 "μs"])
def test_name_pattern_refuses(bad):
    assert not NAME.match(bad)
