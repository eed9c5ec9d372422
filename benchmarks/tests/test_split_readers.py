"""The readers of the launch record and of the three-way node split, on
observations made by hand, and the manifest's entries for them."""

import importlib
import json
import os

import pytest

from conftest import ROOT, load

NEW = ("launch_host_ms", "launch_device_ms", "launch_ready_at_poll_pct",
       "launch_service_max_ms", "ship_padding_pct", "node_self_max_pct",
       "node_blocked_max_pct", "source_self_pct", "idle_ship_starved_pct")
MIXES = {"sat": ("throughput_eps", ["pipe_cb.sat", "ysb_kf.sat",
                                    "sum_cb.sat"]),
         "paced": ("latency_p50_ms", ["pipe_cb.paced"])}


def reader(name):
    return importlib.import_module(f"layer_metrics.readers.{name}")


def manifest_entry(name):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return next(x for x in json.load(f)["per_layer"] if x["name"] == name)


@pytest.mark.parametrize("mix", sorted(MIXES))
@pytest.mark.parametrize("metric", NEW)
def test_new_entry_has_its_file_reader_unit_and_moves(metric, mix):
    moves, cells = MIXES[mix]
    entry = manifest_entry(f"{metric}.{mix}")
    spec = load("layer_metrics", f"{metric}.{mix}.json")
    for k in ("name", "unit", "better", "source", "layer", "moves"):
        assert spec[k] == entry[k], k
    assert entry["moves"] == moves and entry["workloads"] == cells
    assert entry["unit"] == ("ms" if metric.endswith("_ms") else "%")
    assert hasattr(reader(spec["reader"]), "read") and spec["what"]


def test_new_entries_stand_in_one_unbroken_block_in_their_order():
    """Wherever the block stands: later PRs append their own entries after
    it, and none may come between."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        names = [x["name"] for x in json.load(f)["per_layer"]]
    new = [f"{m}.{mix}" for mix in ("sat", "paced") for m in NEW]
    at = names.index(new[0])
    assert names[at:at + len(new)] == new


# -- observations made by hand ------------------------------------------------

def obs_of(tmp_path, monkeypatch, records=None, **over):
    from layer_metrics.readers import launch_file
    monkeypatch.setattr(launch_file, "BENCH", str(tmp_path))
    if records is not None:
        d = tmp_path / "out" / "cell.x" / "nodes"
        d.mkdir(parents=True)
        with open(d / "launches.jsonl", "w") as f:
            for r in records:
                f.write(json.dumps(r) + "\n")
    obs = {"cell": {"name": "cell.x"}, "trace": None, "nodes": [],
           "profile_spans": {}, "slice_counters": {},
           "gen": {"ran_s": 1.0}}
    obs.update(over)
    return obs


def span(phase, t0_ms, t1_ms, launch=None, shard=None, cause=None, **extra):
    return {"phase": phase, "t0_ns": int(t0_ms * 1e6),
            "t1_ns": int(t1_ms * 1e6), "launch": launch, "shard": shard,
            "cause": cause, **extra}


def test_launch_host_is_the_three_phases_over_the_dispatches(tmp_path,
                                                             monkeypatch):
    obs = obs_of(tmp_path, monkeypatch, profile_spans={
        "launch_take": (0.2, 100), "device_put": (0.9, 100),
        "dispatch": (0.3, 100), "harvest_wait": (5.0, 100)})
    got = reader("launch_host").read(obs, {})
    assert got["value"] == pytest.approx(2.0 + 9.0 + 3.0)
    assert "device_put 9.000" in got["note"] and "100 launches" in got["note"]
    del obs["profile_spans"]["device_put"]
    assert reader("launch_host").read(obs, {}) is None


def test_launch_device_by_family_and_nothing_for_an_unnamed_step(tmp_path,
                                                                 monkeypatch):
    obs = obs_of(tmp_path, monkeypatch, trace={"executables": {
        "jit_wf_step_regular(123)": (0.030, 10),
        "jit_wf_step_append_eval(9)": (0.050, 10),
        "jit_something_else(1)": (9.0, 1)}})
    got = reader("launch_device").read(obs, {})
    assert got["value"] == pytest.approx(4.0)
    assert "jit_wf_step_append_eval 5.000 ms x 10" in got["note"]
    assert "something_else" not in got["note"]
    obs["trace"] = {"executables": {"jit_step(5)": (0.03, 10)}}
    assert reader("launch_device").read(obs, {}) is None    # the parent
    obs["trace"] = None
    assert reader("launch_device").read(obs, {}) is None    # a rehearsal


def test_ready_at_poll_and_the_wait_of_the_others(tmp_path, monkeypatch):
    records = [span("harvest_wait", 10, 10.1, 1, 0, ready=True),
               span("harvest_wait", 20, 23.0, 2, 0, ready=False),
               span("harvest_wait", 30, 31.0, 3, 0, ready=False),
               span("harvest_wait", 5000, 5009, 4, 0, ready=False)]
    obs = obs_of(tmp_path, monkeypatch, records, slice_counters={
        "launches": 8.0, "launches_ready_at_poll": 6.0})
    got = reader("ready_at_poll").read(obs, {})
    assert got["value"] == pytest.approx(75.0)
    # the fourth began after the window (1 s from the first record)
    assert "2 others blocked 2.000 ms" in got["note"]
    obs["slice_counters"] = {}
    assert reader("ready_at_poll").read(obs, {}) is None


def test_longest_service_names_the_launch_and_what_held_it(tmp_path,
                                                           monkeypatch):
    records = [
        span("dispatch", 0, 1, 7, 0, 5), span("harvest_wait", 3, 4, 7, 0, 5,
                                              ready=True),
        span("dispatch", 10, 11, 9, 1, 8),
        span("ship_idle", 11.5, 40, None, 1),           # its thread waited
        span("device_put", 40, 45, 12, 1, 8),           # then shipped another
        span("ship_idle", 12, 30, None, 0),             # the other thread
        span("harvest_wait", 46, 47, 9, 1, 8, ready=True),
        span("dispatch", 2000, 2001, 20, 0, 5),         # after the window
        span("harvest_wait", 9000, 9001, 20, 0, 5, ready=True)]
    obs = obs_of(tmp_path, monkeypatch, records)
    got = reader("launch_service_max").read(obs, {})
    assert got["value"] == pytest.approx(36.0)
    note = got["note"]
    assert "launch 9 on ship thread 1" in note and "call 8" in note
    assert "ship_idle 28.500" in note and "device_put 5.000" in note
    assert "ready at harvest True" in note and "2 launches" in note
    assert reader("launch_service_max").read(
        obs_of(tmp_path / "none", monkeypatch), {}) is None


def test_ship_padding(tmp_path, monkeypatch):
    obs = obs_of(tmp_path, monkeypatch, slice_counters={
        "rows_shipped": 1000.0, "rows_live": 750.0})
    assert reader("ship_padding").read(obs, {})["value"] == pytest.approx(25)
    obs["slice_counters"] = {"rows_shipped": 1000.0}        # the parent
    assert reader("ship_padding").read(obs, {}) is None


NODES = [
    {"node": "g_00_src+map+filter", "rcv_batches": 0, "alive_sec": 10.0,
     "svc_time_ms_total": 9900.0, "self_ms_total": 7900.0,
     "blocked_ms_total": 2000.0, "idle_ms_total": 0.0,
     "blocked_max_ms": 40.0, "blocked_max_inbox": "merge",
     "fused_svc_ms": {"map": 3000.0, "filter": 2500.0}},
    {"node": "g_01_emitter", "rcv_batches": 50, "alive_sec": 10.0,
     "svc_time_ms_total": 8800.0, "self_ms_total": 6000.0,
     "blocked_ms_total": 2800.0, "idle_ms_total": 1100.0,
     "blocked_max_ms": 90.0, "blocked_max_inbox": "worker.1",
     "fused_svc_ms": {}},
    {"node": "g_02_worker", "rcv_batches": 50, "alive_sec": 10.0,
     "svc_time_ms_total": 5000.0, "self_ms_total": 5000.0,
     "blocked_ms_total": 0.0, "idle_ms_total": 4900.0,
     "blocked_max_ms": 0.1, "blocked_max_inbox": "collector",
     "fused_svc_ms": {}}]


def test_node_split_names_the_busy_node_and_the_blocked_one(tmp_path,
                                                            monkeypatch):
    obs = obs_of(tmp_path, monkeypatch, nodes=NODES)
    busy = reader("node_split").read(obs, {"field": "self"})
    # the source's self is its fused stages (55%), under the emitter's 60%
    assert busy["value"] == pytest.approx(60.0)
    assert "g_01_emitter" in busy["note"] and "idle 11.0%" in busy["note"]
    blocked = reader("node_split").read(obs, {"field": "blocked"})
    assert blocked["value"] == pytest.approx(28.0)
    assert "longest put 90.000 ms" in blocked["note"]
    assert "worker.1" in blocked["note"]
    old = [{"node": "x", "rcv_batches": 3, "alive_sec": 1.0,
            "svc_time_ms_total": 10.0}]                     # the parent's log
    assert reader("node_split").read(
        obs_of(tmp_path / "p", monkeypatch, nodes=old),
        {"field": "self"}) is None


def test_source_self_is_the_fused_stages_share(tmp_path, monkeypatch):
    obs = obs_of(tmp_path, monkeypatch, nodes=NODES)
    got = reader("source_self").read(obs, {})
    assert got["value"] == pytest.approx(55.0)
    assert "map 30.0%, filter 25.0%" in got["note"]
    assert "blocked 20.0%" in got["note"] and "merge" in got["note"]
    assert reader("source_self").read(
        obs_of(tmp_path / "p", monkeypatch, nodes=NODES[1:]), {}) is None


def test_idle_starved_returns_nothing_without_a_trace(tmp_path, monkeypatch):
    obs = obs_of(tmp_path, monkeypatch)
    assert reader("idle_starved").read(obs, {}) is None
    obs["trace"] = {"executables": {}}      # a trace, but no file under out/
    assert reader("idle_starved").read(obs, {}) is None
