"""The readers the skyline cell brought, on observations made by hand: the
operations model, the share of the vector peak, the padding of a user's
window function, the resident launch diagnostics by name; and what each gives
a program that lacks the counters (the parent of the PR that added them):
nothing, without raising."""

import importlib
import types

import pytest

from conftest import load
from harness import ops_model_skyline as model


def reader(name):
    return importlib.import_module(f"layer_metrics.readers.{name}")


def _obs(executables=None, counters=None, resident=None):
    trace = None if executables is None else {"executables": executables}
    return {"trace": trace, "slice_counters": counters or {},
            "resident": resident or {"dispatches": 3}}


STEP = "jit_wf_step_multi(123)"
COUNTERS = {"udf_windows": 80.0, "udf_rows": 80 * 51200.0,
            "udf_cells": 80 * 65536.0}


def test_operations_of_real_lengths_and_their_lower_bound():
    assert model.skyline_ops([10, 20]) == 9 * (100 + 400)
    assert model.skyline_ops([]) == 0
    # windows of one length: the bound is the count
    assert model.skyline_ops_at_least(300, 3) == model.skyline_ops([100] * 3)
    # ... of different lengths: below it
    assert model.skyline_ops_at_least(30, 2) < model.skyline_ops([10, 20])
    assert model.skyline_ops_at_least(0, 0) == 0
    assert model.vector_share_pct(6.144e12, 2.0, 6.144e12) == 50.0
    assert model.vector_share_pct(1.0, 0.0, 6.144e12) is None


def test_vector_peaks_state_their_derivation_for_the_kinds_peaks_json_has():
    table = load("harness", "peaks_vector.json")
    assert "assumed" in table["source"] and "UPPER" in table["source"]
    assert set(table["by_device_kind"]) \
        == set(load("harness", "peaks.json")["by_device_kind"])
    for peak in table["by_device_kind"].values():
        assert peak["vector_op_per_s"] == 8 * 128 * 4 * 1.5e9


def test_skyline_roofline_on_a_hand_made_slice(monkeypatch):
    import jax
    monkeypatch.setattr(jax, "devices", lambda: [types.SimpleNamespace(
        device_kind="TPU v5 lite")])
    rd = reader("skyline_roofline")
    got = rd.read(_obs({STEP: (2.0, 80), "jit_other(1)": (9.0, 9)},
                       COUNTERS), {"family": "multi"})
    want = 100.0 * 9 * 80 * 51200.0 ** 2 / 2.0 / 6.144e12
    assert got["value"] == pytest.approx(want) and 0 < got["value"] < 100
    assert "80 windows of 51200 points" in got["note"]
    # a program without the counters, a trace without the family, no trace
    assert rd.read(_obs({STEP: (2.0, 80)}, {"windows": 80.0}),
                   {"family": "multi"}) is None
    assert rd.read(_obs({"jit_other(1)": (9.0, 9)}, COUNTERS),
                   {"family": "multi"}) is None
    assert rd.read(_obs(None, COUNTERS), {"family": "multi"}) is None


def test_skyline_roofline_reports_nothing_on_a_device_without_a_peak():
    got = reader("skyline_roofline").read(
        _obs({STEP: (2.0, 80)}, COUNTERS), {"family": "multi"})
    assert got is None                       # the tests' device is a CPU


def test_udf_padding_is_one_minus_rows_over_cells():
    rd = reader("udf_padding")
    got = rd.read(_obs(counters=COUNTERS), {})
    assert got["value"] == pytest.approx(100.0 * (1 - 51200 / 65536))
    assert rd.read(_obs(counters={"rows_shipped": 5.0}), {}) is None
    assert rd.read(_obs(), {}) is None


def test_resident_counter_reads_zero_apart_from_absent():
    rd = reader("resident_counter")
    params = {"counter": "udf_step_builds"}
    assert rd.read(_obs(resident={"udf_step_builds": 0}), params) == 0.0
    assert rd.read(_obs(resident={"udf_step_builds": 6}), params) == 6.0
    assert rd.read(_obs(resident={"dispatches": 9}), params) is None


@pytest.mark.parametrize("name", [
    "launch_device_ms.sky", "udf_padding_pct.sky", "udf_step_builds.sky",
    "skyline_roofline.sky", "launch_host_ms.sky", "node_self_max_pct.sky",
    "node_blocked_max_pct.sky"])
def test_each_new_entry_lists_the_skyline_cell_alone(name):
    import json
    import os
    from conftest import ROOT
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        entry = next(x for x in json.load(f)["per_layer"]
                     if x["name"] == name)
    spec = load("layer_metrics", f"{name}.json")
    assert entry["workloads"] == ["spatial_wf.paced"]
    assert entry["moves"] == spec["moves"] == "latency_p50_ms"
    assert hasattr(reader(spec["reader"]), "read") and spec["what"]
