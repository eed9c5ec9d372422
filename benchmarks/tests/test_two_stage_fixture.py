"""A fixture configuration whose first window stage is a host ``count`` and
whose second is on the device (``fixtures/two_stage``; it is in no manifest
but the one this test writes): its reference against a brute-force loop, and
one rehearsal run of it through ``run.py`` -- with the list form of
``expected_core`` it runs and is correct, with the string form it exits 4."""

import importlib.util
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from conftest import BENCH, ROOT
from harness import generator

FIXTURE = os.path.join(BENCH, "tests", "fixtures", "two_stage")
CELL = "count_sum.sat"


def _oracle():
    spec = importlib.util.spec_from_file_location(
        "count_sum_oracle",
        os.path.join(FIXTURE, "configs", "count_sum_oracle.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _cfg():
    with open(os.path.join(FIXTURE, "configs", "count_sum.json")) as f:
        return json.load(f)


@pytest.mark.parametrize("chunk,bases,rate", [
    (16 * 5, [0, 7, 19, 40, 41, 90, 1000], None),
    (16 * 3, [3 * i for i in range(13)], None),
    (16 * 4, [generator.chunk_base_us(j, 64, 50000) for j in range(9)], 50000),
    (16, [5], None),
])
def test_fixture_reference_equals_brute_force(chunk, bases, rate):
    oracle, cfg = _oracle(), _cfg()
    cfg["shapes"].update(count_win=8, count_slide=2, sum_win=4, sum_slide=3)
    log = {"chunk": chunk, "base_us": np.asarray(bases, dtype=np.int64),
           "off_us": generator.due_offsets_us(chunk, rate)}
    fast, slow = oracle.expected(cfg, 3, log), oracle.brute_force(cfg, 3, log)
    assert len(slow["key"]) > 0
    for col in slow:
        assert np.array_equal(fast[col], slow[col]), col
    never = fast["_closes_at_us"] == oracle.NEVER
    assert never.any()


def _tree(tmp_path, expected_core=None):
    """A checkout's worth of benchmark with the fixture laid over it and a
    manifest that lists the fixture's cell."""
    bench = tmp_path / "benchmarks"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns(
        "out", "__pycache__", "tests", "testdata"))
    for sub in ("configs", "workloads"):
        for fn in os.listdir(os.path.join(FIXTURE, sub)):
            shutil.copy(os.path.join(FIXTURE, sub, fn), bench / sub / fn)
    cfg_path = bench / "configs" / "count_sum.json"
    cfg = json.loads(cfg_path.read_text())
    if expected_core is not None:
        cfg["expected_core"] = expected_core
        cfg_path.write_text(json.dumps(cfg))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cell = json.loads((bench / "workloads" / f"{CELL}.json").read_text())
    manifest["configs"].append({
        "name": "count_sum", "source": cfg["source"],
        "file": "benchmarks/configs/count_sum.json", "reduced": [],
        "why": "fixture"})
    manifest["workloads"].append({k: cell[k] for k in (
        "name", "config", "traffic", "chips", "why")})
    sat = next(w["name"] for w in manifest["workloads"]
               if w["traffic"] == "sat")
    for metric in manifest["end_to_end"] + manifest["per_layer"]:
        if sat in metric.get("workloads", []):
            metric["workloads"].append(CELL)
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(manifest))
    return bench


def _run(bench, trace=0):
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=ROOT)
    return subprocess.run(
        [sys.executable, str(bench / "run.py"), "--workload", CELL,
         "--seed", str(2**31 + 9), "--seconds", "2", "--trace", str(trace)],
        cwd=str(bench.parent), env=env, capture_output=True, text=True,
        timeout=300)


def test_the_list_form_lets_a_host_count_stage_through(tmp_path):
    proc = _run(_tree(tmp_path))
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    lines = proc.stdout.strip().splitlines()
    result = json.loads(lines[-1])
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] > 0
    assert set(result["metrics"]) == {"throughput_eps", "setup_s"}
    assert any("1 x LazySlidingCore (host) > 1 x NativeResidentCore" in ln
               for ln in lines)
    assert all(v == {"value": 0, "limit": 0}
               for v in result["check"].values())
    assert "check wrong.total = 0 (limit 0)" in proc.stderr


def test_the_string_form_exits_4_on_the_same_graph(tmp_path):
    proc = _run(_tree(tmp_path, expected_core="NativeResidentCore"))
    assert proc.returncode == 4, proc.stdout[-2000:] + proc.stderr[-2000:]
    assert "device path" in proc.stdout
    assert not proc.stdout.strip().splitlines()[-1].startswith("{")


def test_a_list_that_calls_the_host_stage_a_device_stage_exits_4(tmp_path):
    stages = _cfg()["expected_core"]
    stages[0]["device"] = True
    stages[0]["workers"], stages[1]["workers"] = 1, 1
    bench = _tree(tmp_path, expected_core=stages)
    cfg_path = bench / "configs" / "count_sum.json"
    cfg = json.loads(cfg_path.read_text())
    cfg["shapes"]["device_window_workers"] = 2
    cfg_path.write_text(json.dumps(cfg))
    proc = _run(bench)
    assert proc.returncode == 4, proc.stdout[-2000:] + proc.stderr[-2000:]
