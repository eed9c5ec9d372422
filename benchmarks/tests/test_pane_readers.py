"""The readers the pane-form skyline cell brought, on observations made by
hand: how full the slots of a container-valued result are, a stage's worker
by its name, what the second stage adds to a result's wait (from hand-made
launch records); and what each gives a program that lacks the counters, the
node or the records (the parent of the PR that added them): nothing, without
raising.  The cell's other metrics reuse readers that have their own tests."""

import importlib
import json
import os

import pytest

from conftest import BENCH, load


def reader(name):
    return importlib.import_module(f"layer_metrics.readers.{name}")


CFG = {"shapes": {"cap": 64, "win_us": 1_000_000, "slide_us": 50_000}}


def _node(name, **kw):
    base = {"node": name, "alive_sec": 10.0, "self_ms_total": 1000.0,
            "blocked_ms_total": 100.0, "idle_ms_total": 8900.0,
            "rcv_batches": 200, "fused_svc_ms": {}}
    base.update(kw)
    return base


def test_pane_fill_is_slots_held_over_slots():
    nodes = [_node("g_01_sky_plq.0", pane_results=100, pane_points_kept=1200,
                   pane_overflow=0),
             _node("g_01_sky_plq.1", pane_results=100, pane_points_kept=1360,
                   pane_overflow=0),
             _node("g_05_sky_wlq.0")]
    got = reader("pane_fill").read({"nodes": nodes, "cfg": CFG},
                                   {"cap": "cap"})
    assert got["value"] == pytest.approx(100.0 * 2560 / (200 * 64))
    assert "12.80 a result" in got["note"] and "0 results over" in got["note"]
    # a program whose nodes keep no such counter; results but none counted
    assert reader("pane_fill").read(
        {"nodes": [_node("g_01_sky_plq.0")], "cfg": CFG},
        {"cap": "cap"}) is None
    assert reader("pane_fill").read(
        {"nodes": [_node("a", pane_results=0, pane_points_kept=0)],
         "cfg": CFG}, {"cap": "cap"}) is None
    assert reader("pane_fill").read({"nodes": [], "cfg": CFG},
                                    {"cap": "cap"}) is None


def test_a_stages_worker_is_found_by_its_name():
    nodes = [_node("g_01_sky_plq.0", self_ms_total=3000.0),
             _node("g_05_sky_wlq.0", self_ms_total=150.0,
                   blocked_ms_total=10.0, idle_ms_total=9840.0),
             # a source has no inbox: never a stage's worker
             _node("g_00_wlq_gen.0", rcv_batches=0, self_ms_total=9000.0)]
    got = reader("node_named").read({"nodes": nodes}, {"name_has": "_wlq"})
    assert got["value"] == pytest.approx(1.5)
    assert "g_05_sky_wlq.0" in got["note"] and "0.750 ms a batch" in got["note"]
    assert reader("node_named").read({"nodes": nodes[:1]},
                                     {"name_has": "_wlq"}) is None
    assert reader("node_named").read({"nodes": []},
                                     {"name_has": "_wlq"}) is None


def _records(path, lines):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        for line in lines:
            f.write(json.dumps(line) + "\n")


def _span(phase, t0_ms, t1_ms, **extra):
    return dict({"phase": phase, "t0_ns": int(t0_ms * 1e6),
                 "t1_ns": int(t1_ms * 1e6), "launch": None, "shard": None,
                 "cause": None}, **extra)


@pytest.fixture
def cell_dir():
    """A cell's output directory nobody else uses."""
    name = "test_pane_readers.cell"
    path = os.path.join(BENCH, "out", name, "nodes", "launches.jsonl")
    yield name, path
    import shutil
    shutil.rmtree(os.path.join(BENCH, "out", name), ignore_errors=True)


def test_the_second_stage_wait_from_hand_made_records(cell_dir):
    name, path = cell_dir
    # panes 0..24 leave their workers 80 ms after their end, pane p ends at
    # (p + 1) * 50 ms; window w (panes w..w+19) leaves 1 ms after pane w+20
    lines = [_span("dispatch", 0.0, 0.1)]
    for p in range(25):
        t = (p + 1) * 50 + 80
        lines.append(_span("pane_emit", t, t + 0.2, key=0, ids=[p], rows=1))
    for w in range(5):
        t = (w + 21) * 50 + 80 + 1
        lines.append(_span("window_emit", t - 0.5, t, key=0, ids=[w], rows=1))
    _records(path, lines)
    obs = {"cell": {"name": name}, "cfg": CFG, "gen": {"ran_s": 50.0}}
    got = reader("stage_wait").read(obs, {})
    # from the LAST pane (w + 19), one pane period before the one that fires
    assert got["value"] == pytest.approx(51.0)
    assert "5 windows" in got["note"] and "20 panes of 50000 us" in got["note"]
    assert "NEXT pane's result" in got["note"] and "p50 1.000" in got["note"]
    # a record of a batch of results names every id it carried: window 5's
    # last pane, 24, is on record too
    lines[-1] = _span("window_emit", 1380.5, 1381.0, key=0, ids=[4, 5],
                      rows=2)
    _records(path, lines)
    assert "6 windows" in reader("stage_wait").read(obs, {})["note"]


def test_the_second_stage_wait_reads_nothing_from_a_program_without_it(
        cell_dir):
    name, path = cell_dir
    obs = {"cell": {"name": name}, "cfg": CFG, "gen": {"ran_s": 50.0}}
    assert reader("stage_wait").read(obs, {}) is None        # no file
    _records(path, [_span("dispatch", 0.0, 0.1),
                    _span("harvest_wait", 0.1, 27.0, ready=False)])
    assert reader("stage_wait").read(obs, {}) is None        # no such span
    _records(path, [_span("pane_emit", 1.0, 1.1, key=0, ids=[0], rows=1)])
    assert reader("stage_wait").read(obs, {}) is None        # one stage only
    _records(path, [_span("pane_emit", 1.0, 1.1, key=0, ids=[0], rows=1),
                    _span("window_emit", 2.0, 2.1, key=0, ids=[7], rows=1)])
    assert reader("stage_wait").read(obs, {}) is None        # no pair


PF = ["launch_device_ms", "launch_host_ms", "skyline_roofline",
      "udf_padding_pct", "udf_step_builds", "result_wake_pct",
      "node_self_max_pct", "node_blocked_max_pct", "pane_fill_pct",
      "wlq_self_pct", "second_stage_wait_ms"]


@pytest.mark.parametrize("stem", PF)
def test_the_cells_metric_files_move_its_latency_and_list_it_alone(stem):
    spec = load("layer_metrics", f"{stem}.pf.json")
    manifest = load("..", "BENCHMARK.json")
    entry = next(x for x in manifest["per_layer"] if x["name"] == spec["name"])
    assert entry["moves"] == spec["moves"] == "latency_p50_ms"
    assert entry["workloads"] == ["spatial_pf.paced"]
    assert spec["what"] and hasattr(reader(spec["reader"]), "read")
    # where a sibling of the whole-window skyline cell exists, the same
    # reader and parameters: one yardstick over both
    sibling = os.path.join(BENCH, "layer_metrics", f"{stem}.sky.json")
    if os.path.isfile(sibling):
        with open(sibling) as f:
            sky = json.load(f)
        assert (sky["reader"], sky["params"], sky["unit"], sky["better"],
                sky["layer"]) == (spec["reader"], spec["params"],
                                  spec["unit"], spec["better"],
                                  spec["layer"])
