"""Each cell end to end at a tiny size on the CPU (a rehearsal), with the
last line parsed against the contract's keys; and the whole of a run with the
timed path broken underneath, which has to come out as not correct."""

import json
import os
import subprocess
import sys

import pytest

from conftest import BENCH, ROOT


def _cells():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [w["name"] for w in json.load(f)["workloads"]]


def _manifest_metrics(cell, kind):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        m = json.load(f)
    return {x["name"]: x["unit"] for x in m[kind]
            if cell in x.get("workloads", [cell])}


def _run(cell, trace, seed=2**31 + 5, seconds=2):
    env = dict(os.environ, JAX_PLATFORMS="cpu", BENCH_RUN="ignored")
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", str(seed), "--seconds", str(seconds), "--trace",
         str(trace)], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert proc.returncode == 0, proc.stdout[-2000:] + proc.stderr[-2000:]
    return proc.stdout.strip().splitlines(), proc.stderr.strip().splitlines()


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", _cells())
def test_cell_runs_and_its_last_line_meets_the_contract(cell, trace):
    lines, errors = _run(cell, trace)
    result = json.loads(lines[-1])
    assert list(result) == ["correct", "attempted", "failed", "metrics",
                            "device", "check"]  # no breakdown without a chip
    assert result["correct"] is True
    assert result["attempted"] > 0 and result["failed"] == 0
    dev = result["device"]
    assert dev["platform"] == "cpu" and dev["count"] >= 1
    assert set(dev) == {"platform", "kind", "count", "memory_peak_bytes"}
    listed = _manifest_metrics(cell, "per_layer" if trace else "end_to_end")
    assert result["metrics"], "a run reports at least one metric"
    for name, m in result["metrics"].items():
        assert name in listed and m["unit"] == listed[name]
        assert isinstance(m["value"], float)
    if trace:
        # a CPU run reports no trace-derived metric
        assert not any(("roofline" in n or "device_idle" in n)
                       for n in result["metrics"])
    else:
        assert set(result["metrics"]) == set(listed)
        assert all(m["value"] > 0 for m in result["metrics"].values())
    # every number compared stands beside its limit
    checks = [ln for ln in lines if ln.startswith("check ")]
    assert len(checks) >= 5 and all("(limit 0)" in ln for ln in checks)
    # ... in the result's line too, last there, and last on standard error
    assert len(result["check"]) == len(checks)
    assert all(v == {"value": 0, "limit": 0} for v in result["check"].values())
    assert errors[-len(checks):] == checks


def test_no_accelerator_and_no_rehearsal_exits_non_zero():
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    cell = _cells()[0]
    proc = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", cell,
         "--seed", "1", "--seconds", "1", "--trace", "0"], cwd=ROOT, env=env,
        capture_output=True, text=True, timeout=300)
    assert proc.returncode != 0
    assert not proc.stdout.strip().splitlines()[-1].startswith("{")


BREAKS = {
    "a value altered where it is produced": lambda res: _alter(res),
    "a result dropped": lambda res: res[1:],
    "a result delivered twice": lambda res: _twice(res),
}


def _alter(res):
    field = [f for f in res.dtype.names
             if f not in ("key", "id", "ts", "marker")][-1]
    res[field][0] += 1
    return res


def _twice(res):
    import numpy as np
    return np.concatenate([res[:1], res])


@pytest.mark.parametrize("how", sorted(BREAKS))
@pytest.mark.parametrize("cell", ["pipe_cb.sat", "ysb_kf.sat"])
def test_a_broken_timed_path_comes_out_not_correct(cell, how, monkeypatch,
                                                   capsys):
    """Drives a whole run in this process, with the window core's harvest
    (where every window result is produced) broken once."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    import run
    from windflow_tpu.patterns.native_core import NativeResidentCore
    sound = NativeResidentCore._harvest
    state = {"armed": False, "done": False}

    def broken(self, harvested):
        res = sound(self, harvested)
        if state["armed"] and not state["done"] and len(res) > 1:
            state["done"] = True
            return BREAKS[how](res)
        return res

    monkeypatch.setattr(NativeResidentCore, "_harvest", broken)
    # arm only once the warm-up pass is over: the measured pipeline breaks
    from harness import generator
    start = generator.Generator.__call__

    def arming_call(self, shipper):
        state["armed"] = self.tail_seconds > 0
        return start(self, shipper)

    monkeypatch.setattr(generator.Generator, "__call__", arming_call)
    rc = run.main(["--workload", cell, "--seed", "11", "--seconds", "2",
                   "--trace", "0"])
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0 and state["done"]
    result = json.loads(out[-1])
    assert result["correct"] is False
    assert any(ln.startswith("check ") and "(limit 0)" in ln
               and " = 0 " not in ln for ln in out)
