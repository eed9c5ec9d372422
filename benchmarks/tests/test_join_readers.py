"""What the join cell brought, on observations made by hand: the bytes model
of the join step on a hand-sized window, its share of the roofline, a
counter a second of its node's life; and what each reader gives a program
that lacks the counters or the family (the parent of the PR that added
them): nothing, without raising.  The cell's other metrics reuse readers
that have their own tests (``family_device``, ``profile_span``,
``node_ratio``, ``latency_tail``), held here to the join's own names."""

import importlib

import pytest

from conftest import load
from harness import bytes_model_join


def reader(name):
    return importlib.import_module(f"layer_metrics.readers.{name}")


def _node(name, **kw):
    base = {"node": name, "alive_sec": 50.0, "self_ms_total": 1000.0,
            "blocked_ms_total": 100.0, "idle_ms_total": 8900.0,
            "rcv_batches": 200, "fused_svc_ms": {}}
    base.update(kw)
    return base


def test_bytes_model_on_a_hand_sized_window():
    # 8 rows (2 persons, 6 auctions), 5 matches, five ring columns, four
    # output columns: every pass once, 4 bytes a cell
    parts = bytes_model_join.join_bytes(8, 5, ring_cols=5, out_cols=4)
    assert parts == {"slice": 4 * 5 * 2 * 8, "sort": 4 * 6 * 2 * 8,
                     "search": 4 * 6 * 8, "compact": 4 * 5 * 2 * 8,
                     "out": 4 * 4 * 2 * 5}
    assert sum(parts.values()) == 320 + 384 + 192 + 320 + 160
    # more columns carried, more bytes; no match, no output
    assert bytes_model_join.join_bytes(8, 0)["out"] == 0
    wide = bytes_model_join.join_bytes(8, 5, ring_cols=7, out_cols=6)
    assert wide["slice"] == 4 * 7 * 2 * 8 and wide["out"] == 4 * 6 * 2 * 5


TRACE = {"executables": {"jit_wf_step_multi(123)": (0.5, 1),
                         "jit_wf_step_append(77)": (0.004, 8),
                         "jit_other(1)": (9.0, 3)}}
PEAKS = {"hbm_bytes_per_s": 819e9}
PARAMS = {"family": "multi", "ring_cols": 5, "out_cols": 4}


def test_join_roofline_is_needed_bytes_over_time_over_peak():
    counters = {"join_windows": 1.0, "join_left_rows": 4e6,
                "join_right_rows": 12e6, "join_results": 11.9e6}
    got = reader("join_roofline").read(
        {"trace": TRACE, "slice_counters": counters, "peaks": PEAKS}, PARAMS)
    n_bytes = sum(bytes_model_join.join_bytes(16e6, 11.9e6).values())
    assert got["value"] == pytest.approx(100.0 * n_bytes / 0.5 / 819e9)
    assert got["value"] < 100.0
    assert "1 windows of 16000000 rows and 11900000 matches" in got["note"]
    assert "0.500000 s of the family's 1 launches" in got["note"]
    # the launch in the slice, its harvest behind it: the matches count 0
    early = dict(counters, join_results=0.0)
    assert reader("join_roofline").read(
        {"trace": TRACE, "slice_counters": early, "peaks": PEAKS},
        PARAMS)["value"] < got["value"]


@pytest.mark.parametrize("obs", [
    {"trace": None, "slice_counters": {"join_windows": 1.0}},
    {"trace": TRACE, "slice_counters": {}},                 # the parent
    {"trace": TRACE, "slice_counters": {"join_windows": 0.0}},
    {"trace": {"executables": {"jit_wf_step_append(7)": (0.1, 4)}},
     "slice_counters": {"join_windows": 1.0, "join_left_rows": 5.0}},
])
def test_join_roofline_without_something_to_read(obs):
    assert reader("join_roofline").read(dict(obs, peaks=PEAKS),
                                        PARAMS) is None


def test_node_rate_is_a_counter_over_its_nodes_life():
    nodes = [_node("g_02_q8_join.0", join_results=60_000_000,
                   alive_sec=53.5),
             _node("g_03_q8_sink.0", alive_sec=54.0)]
    got = reader("node_rate").read({"nodes": nodes},
                                   {"counter": "join_results"})
    assert got["value"] == pytest.approx(60_000_000 / 53.5)
    assert "g_02_q8_join.0" in got["note"]
    assert reader("node_rate").read(
        {"nodes": [_node("a")]}, {"counter": "join_results"}) is None
    assert reader("node_rate").read({"nodes": []},
                                    {"counter": "join_results"}) is None


def test_the_join_cells_metrics_name_what_the_program_records():
    """Each of the cell's new metric files against the reader it names, on
    an observation that holds what the program writes under those names."""
    nodes = [_node("g_02_q8_join.0", join_slots_filled=48_000_000,
                   join_slots_asked=4 * 25_165_824, join_results=48_000_000)]
    obs = {"trace": TRACE, "nodes": nodes, "peaks": PEAKS,
           "profile_spans": {"join_stage": (6.0, 3800),
                             "join_unpack": (2.5, 48)},
           "slice_counters": {"join_windows": 1.0, "join_left_rows": 4e6,
                              "join_right_rows": 12e6, "join_results": 12e6},
           "window_s": 50.0, "window_workers": 1,
           "latency_ms": [400.0, 500.0, 900.0]}
    want = {"join_device_ms.q8": 500.0, "join_stage_pct.q8": 12.0,
            "join_unpack_pct.q8": 5.0,
            "join_slot_fill_pct.q8": 100.0 * 48e6 / (4 * 25_165_824),
            "join_results_per_s.q8": 48e6 / 50.0,
            "result_wait_p50_ms.q8": 500.0}
    for name, value in want.items():
        spec = load("layer_metrics", f"{name}.json")
        got = reader(spec["reader"]).read(obs, spec["params"])
        got = got["value"] if isinstance(got, dict) else got
        assert got == pytest.approx(value), name
    spec = load("layer_metrics", "join_roofline.q8.json")
    assert 0 < reader(spec["reader"]).read(obs, spec["params"])["value"] < 100
    # ... and on what the parent's program leaves: nothing, no raise
    bare = {"trace": {"executables": {}}, "nodes": [_node("a")],
            "peaks": PEAKS, "profile_spans": {}, "slice_counters": {},
            "window_s": 50.0, "window_workers": 1, "latency_ms": []}
    for name in list(want) + ["join_roofline.q8"]:
        spec = load("layer_metrics", f"{name}.json")
        assert reader(spec["reader"]).read(bare, spec["params"]) is None, name
