"""The readers of the thread's CPU clock (ISSUE 36) on node logs and launch
records made by hand, and the manifest's six entries for them."""

import json
import os

import pytest

from conftest import ROOT, load
from test_split_readers import manifest_entry, obs_of, reader, span

NEW = {"node_offcpu_max_pct": ("graph + engine", "%", "node_offcpu"),
       "self_offcpu_share_pct": ("graph + engine", "%", "node_offcpu"),
       "launch_host_cpu_ms": ("ship path", "ms", "launch_host_cpu")}
MIXES = {"sat": ("throughput_eps", [
    "pipe_cb.sat", "ysb_kf.sat", "q7_highest_bid.sat", "sum_cb.sat",
    "q5_hot_items.sat", "sum_tb_late.sat"]),
         "paced": ("latency_p50_ms", ["pipe_cb.paced"])}


@pytest.mark.parametrize("mix", sorted(MIXES))
@pytest.mark.parametrize("metric", sorted(NEW))
def test_new_entry_has_its_file_reader_unit_and_moves(metric, mix):
    layer, unit, module = NEW[metric]
    moves, cells = MIXES[mix]
    entry = manifest_entry(f"{metric}.{mix}")
    spec = load("layer_metrics", f"{metric}.{mix}.json")
    for k in ("name", "unit", "better", "source", "layer", "moves"):
        assert spec[k] == entry[k], k
    assert (entry["layer"], entry["unit"], spec["reader"]) \
        == (layer, unit, module)
    assert entry["moves"] == moves and entry["workloads"] == cells
    assert entry["source"] == "program_span" and entry["better"] == "lower"
    assert hasattr(reader(module), "read") and spec["what"]


def test_the_six_entries_end_the_list_in_one_block():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        names = [x["name"] for x in json.load(f)["per_layer"]]
    new = [f"{m}.{mix}" for mix in ("sat", "paced")
           for m in ("node_offcpu_max_pct", "self_offcpu_share_pct",
                     "launch_host_cpu_ms")]
    at = names.index(new[0])
    assert names[at:at + len(new)] == new


# -- node logs made by hand ---------------------------------------------------

def node(name, alive_s, self_ms, cpu_ms, blocked=0.0, idle=None, rcv=10,
         fused=None, fused_cpu=None, wait_cpu=0.0, vol=0, invol=0):
    idle = alive_s * 1e3 - self_ms - blocked if idle is None else idle
    return {"node": name, "rcv_batches": rcv, "alive_sec": alive_s,
            "self_ms_total": self_ms, "self_cpu_ms_total": cpu_ms,
            "blocked_ms_total": blocked, "idle_ms_total": idle,
            "wait_cpu_ms_total": wait_cpu, "fused_svc_ms": fused or {},
            "fused_cpu_ms": fused_cpu or {}, "ctx_voluntary": vol,
            "ctx_involuntary": invol}


def nodes():
    return [
        # a source: generate() sleeps most of its life, and only its two
        # fused stages count (300 ms, 60 of them off the CPU)
        node("g_00_src", 1.0, 900.0, 250.0, blocked=100.0, idle=0.0, rcv=0,
             fused={"map.0": 200.0, "filter.0": 100.0},
             fused_cpu={"map.0": 150.0, "filter.0": 90.0}, vol=40, invol=3),
        # the busiest node, nearly all of it on the CPU
        node("g_01_emitter", 1.0, 800.0, 780.0, wait_cpu=1.0, vol=10),
        # the node that waits most while in service
        node("g_02_worker", 1.0, 500.0, 100.0, blocked=50.0, wait_cpu=5.0,
             vol=80, invol=7),
        node("g_03_sink", 1.0, 100.0, 100.0)]


def test_largest_off_cpu_names_the_node_and_the_busiest_beside_it(tmp_path,
                                                                  monkeypatch):
    obs = obs_of(tmp_path, monkeypatch, nodes=nodes())
    got = reader("node_offcpu").read(obs, {"over": "node"})
    assert got["value"] == pytest.approx(40.0)
    note = got["note"]
    assert note.startswith("g_02_worker: self 50.0% = on the CPU 10.0% + "
                           "off it 40.0%, blocked 5.0%, idle 45.0%")
    assert "CPU burnt waiting 0.50%" in note
    assert "switches 80 voluntary / 7 involuntary" in note
    assert "5.000 ms off the CPU per voluntary switch" in note
    assert "the node with the largest self: g_01_emitter: self 80.0% = on " \
           "the CPU 78.0% + off it 2.0%" in note


def test_a_source_counts_with_its_fused_stages_only(tmp_path, monkeypatch):
    src = nodes()[0]
    obs = obs_of(tmp_path, monkeypatch, nodes=[src])
    got = reader("node_offcpu").read(obs, {"over": "node"})
    # (200 - 150) + (100 - 90) of 1000 ms, not generate()'s 900 - 250
    assert got["value"] == pytest.approx(6.0)
    assert "g_00_src: self 30.0% = on the CPU 24.0% + off it 6.0%" \
        in got["note"]
    assert "a source: its fused stages only" in got["note"]
    assert "1.500 ms off the CPU per voluntary switch" in got["note"]
    assert "largest self" not in got["note"]        # it is the same node


def test_share_of_all_self_time_off_the_cpu(tmp_path, monkeypatch):
    obs = obs_of(tmp_path, monkeypatch, nodes=nodes())
    got = reader("node_offcpu").read(obs, {"over": "graph"})
    # off 60 + 20 + 400 + 0 of self 300 + 800 + 500 + 100
    assert got["value"] == pytest.approx(100.0 * 480.0 / 1700.0)
    note = got["note"]
    assert "480.0 ms off the CPU of 1700.0 ms self over 4 nodes" in note
    assert "g_02_worker 400.0 ms, g_00_src 60.0 ms, g_01_emitter 20.0 ms" \
        in note
    assert "most CPU burnt waiting: g_02_worker 0.50%" in note


@pytest.mark.parametrize("over", ["node", "graph"])
def test_logs_without_the_cpu_clock_give_nothing(tmp_path, monkeypatch, over):
    old = []
    for n in nodes():
        for k in ("self_cpu_ms_total", "fused_cpu_ms", "wait_cpu_ms_total",
                  "ctx_voluntary", "ctx_involuntary"):
            del n[k]
        old.append(n)
    obs = obs_of(tmp_path, monkeypatch, nodes=old)      # the parent's logs
    assert reader("node_offcpu").read(obs, {"over": over}) is None
    obs = obs_of(tmp_path, monkeypatch, nodes=[])       # no log at all
    assert reader("node_offcpu").read(obs, {"over": over}) is None


# -- launch records made by hand ----------------------------------------------

def cpu_span(phase, t0_ms, t1_ms, cpu_ms, launch=None, shard=0):
    return span(phase, t0_ms, t1_ms, launch, shard, cpu_ns=int(cpu_ms * 1e6))


def test_launch_cpu_is_the_three_phases_cpu_over_the_dispatches(tmp_path,
                                                                monkeypatch):
    records = []
    for i, t in enumerate((0, 100)):
        records += [cpu_span("launch_take", t, t + 1, 0.5, i),
                    cpu_span("device_put", t + 1, t + 5, 1.0, i),
                    cpu_span("dispatch", t + 5, t + 6, 0.9, i),
                    cpu_span("harvest_wait", t + 6, t + 16, 0.1, i),
                    cpu_span("ship_idle", t + 16, t + 90, 0.0)]
    # after the window (1 s from the first record): not counted
    records.append(cpu_span("dispatch", 5000, 5001, 1.0, 9))
    obs = obs_of(tmp_path, monkeypatch, records)
    got = reader("launch_host_cpu").read(obs, {})
    assert got["value"] == pytest.approx(0.5 + 1.0 + 0.9)
    note = got["note"]
    assert "launch_take 1.000 / 0.500, device_put 4.000 / 1.000, " \
           "dispatch 1.000 / 0.900" in note
    assert "harvest_wait 10.000 / 0.100, ship_idle 74.000 / 0.000" in note
    assert "2 launches" in note


def test_cpu_read_on_some_spans_is_scaled_to_the_phases_wall(tmp_path,
                                                             monkeypatch):
    """Where the CPU clock is dear the program reads it on every so-manyth
    span of a phase: the phase's CPU share, from those, times its wall."""
    records = []
    for i in range(4):
        t = 100 * i
        put_cpu = {"cpu_ns": int(1e6)} if i % 2 == 0 else {}
        records += [cpu_span("launch_take", t, t + 1, 0.5, i),
                    span("device_put", t + 1, t + 5, i, 0, **put_cpu),
                    cpu_span("dispatch", t + 5, t + 6, 0.9, i),
                    span("harvest_wait", t + 6, t + 16, i, 0)]
    obs = obs_of(tmp_path, monkeypatch, records)
    got = reader("launch_host_cpu").read(obs, {})
    # device_put: 2 ms of CPU in the 8 ms that were followed, of 16 in all
    assert got["value"] == pytest.approx(0.5 + 1.0 + 0.9)
    assert "device_put 4.000 / 1.000" in got["note"]
    assert "harvest_wait 10.000 / 0.000" in got["note"]     # none followed
    assert "4 launches" in got["note"] and "on 4 / 2 / 4 spans" in got["note"]


def test_records_without_cpu_ns_give_nothing(tmp_path, monkeypatch):
    records = [span("launch_take", 0, 1, 1, 0), span("device_put", 1, 5, 1, 0),
               span("dispatch", 5, 6, 1, 0)]
    obs = obs_of(tmp_path, monkeypatch, records)        # the parent's file
    assert reader("launch_host_cpu").read(obs, {}) is None
    none = obs_of(tmp_path / "none", monkeypatch)       # no file at all
    assert reader("launch_host_cpu").read(none, {}) is None
