"""The ``ysb_kf_eo`` configuration's files: its reference is ``ysb_kf``'s (a
crash changes no expected result) and agrees with the event-by-event loop;
what the guarantee adds reads wrong by name; the file states ``ysb_kf``'s
shapes letter for letter and the recovery policy and kill beside them; and
the ``.eo`` metric files read a recorded set of node logs (a traced chip run
of PR 40: ``fixtures/ysb_kf_eo_nodes.json``, its eight ``.log`` files in one
list)."""

import importlib
import json
import os

import numpy as np
import pytest

from conftest import BENCH, load

from configs import ysb_kf_eo_oracle as eo
from configs import ysb_kf_oracle as plain
from harness import check

NODES = os.path.join(BENCH, "tests", "fixtures", "ysb_kf_eo_nodes.json")


def _small(cfg):
    cfg = json.loads(json.dumps(cfg))
    cfg["shapes"].update(n_campaigns=5, ads_per_campaign=3, win_us=1000,
                         slide_us=1000)
    cfg["stream"]["recurrence_period"] = 60
    return cfg


def _log(n_chunks, chunk, step_us):
    return {"chunk": chunk, "own_ts": False,
            "base_us": np.arange(n_chunks, dtype=np.int64) * step_us,
            "off_us": np.zeros(chunk, dtype=np.int64)}


@pytest.mark.parametrize("name", ["expected", "columns", "brute_force",
                                  "period_events", "id_shift",
                                  "events_of_missing"])
def test_the_reference_is_the_plain_deployments(name):
    assert getattr(eo, name) is getattr(plain, name)


@pytest.mark.parametrize("seed", [0, 41, 2**31 + 9])
def test_expected_agrees_with_the_loop_over_every_event(seed):
    cfg = _small(load("configs", "ysb_kf_eo.json"))
    log = _log(n_chunks=40, chunk=30, step_us=130)
    want = eo.expected(cfg, seed, log)
    brute = eo.brute_force(cfg, seed, log)
    numbers, _ = check.compare(brute, want)
    assert check.verdict(numbers)[0], numbers
    assert len(want["key"]) == len(brute["key"]) > 20


def _table(pairs):
    key, wid = (np.asarray(x, dtype=np.int64) for x in zip(*pairs))
    return {"key": key, "wid": wid, "count": np.ones(len(key), np.int64)}


@pytest.mark.parametrize("got, faults", [
    ([(0, 0), (0, 1), (1, 0)], {}),
    ([(0, 0), (0, 1), (0, 1), (1, 0)], {"duplicates": 1}),
    ([(0, 0), (1, 0)], {"missing": 1}),
    ([(0, 0), (0, 0), (1, 0), (1, 0)], {"duplicates": 2, "missing": 1}),
], ids=["exactly once", "twice", "never", "both"])
def test_a_duplicate_or_a_missing_result_reads_wrong_by_name(got, faults):
    want = _table([(0, 0), (0, 1), (1, 0)])
    numbers, _ = check.compare(_table(got), want)
    assert eo.delivery_faults(numbers) == faults
    assert check.verdict(numbers)[0] is (not faults)


@pytest.mark.parametrize("group", ["shapes", "stream", "precision",
                                   "expected_core", "reduced", "devices",
                                   "reduced_why"])
def test_the_file_states_the_plain_deployments_groups_letter_for_letter(group):
    assert load("configs", "ysb_kf_eo.json")[group] == \
        load("configs", "ysb_kf.json")[group]


def test_the_file_states_the_policy_the_kill_and_the_guarantees():
    cfg = load("configs", "ysb_kf_eo.json")
    policy = {k: v for k, v in cfg["recovery"].items() if k != "why"}
    assert policy == {"epoch_period": 1.0, "checkpoint_dir": None,
                      "snapshot_rings": False, "max_restarts": 3,
                      "restart_backoff": 0.05, "replay_capacity": 1024}
    kill = cfg["kill"]
    assert (kill["worker"], kill["window_index"], kill["offset_us"],
            kill["warmup_window_index"], kill["after_emit"]) == \
        (0, 2, 5_000_000, 0, False)
    text = " ".join(cfg["guarantees"])
    for phrase in ("exactly once at the sink across the crash",
                   "none missing, none twice", "checkpoint every second",
                   "crash survived", "not restarted"):
        assert phrase in text, phrase
    assert "recovery off" not in text
    assert cfg["ship"]["flush_rows"] == 1 << 20      # the library's default
    assert len(cfg["assumed"]) > len(load("configs", "ysb_kf.json")["assumed"])


def _eo_metrics():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        return [m["name"] for m in json.load(f)["per_layer"]
                if m.get("workloads") == ["ysb_kf_eo.sat"]]


def _recorded_nodes():
    with open(NODES) as f:
        return json.load(f)


#: what the node-log readers have to read off the recorded logs
RECORDED = {
    "ckpt_bytes_peak.eo": lambda v: 1e8 < v < 1.2e9,
    "checkpoints_skipped.eo": lambda v: v == 0,
    "restore_ms.eo": lambda v: 50 < v < 20_000,
    "replayed_batches.eo": lambda v: 1 <= v <= 1024,
    "dedup_dropped_batches.eo": lambda v: v == 0,
    "node_restarts.eo": lambda v: v == 1,
    "node_self_max_pct.eo": lambda v: 0 < v <= 100,
    "node_blocked_max_pct.eo": lambda v: 0 <= v <= 100,
    "source_self_pct.eo": lambda v: 0 < v <= 100,
}


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_eo_reader_on_the_recorded_node_logs(name):
    assert name in _eo_metrics()
    spec = load("layer_metrics", f"{name}.json")
    reader = importlib.import_module(f"layer_metrics.readers.{spec['reader']}")
    obs = {"nodes": _recorded_nodes()}
    got = reader.read(obs, spec["params"])
    value = got["value"] if isinstance(got, dict) else got
    assert RECORDED[name](value), (name, value)
    # a program that records none of it (the parent) gives the reader nothing
    bare = [{k: v for k, v in n.items() if k in (
        "node", "rcv_batches", "rcv_tuples")} for n in obs["nodes"]]
    if spec["reader"] in ("node_counter", "node_ratio"):
        assert reader.read({"nodes": bare}, spec["params"]) is None


@pytest.mark.parametrize("span, name", [
    ("checkpoint_drain", "ckpt_drain_pct.eo"),
    ("state_export", "state_export_pct.eo")])
def test_eo_span_share(span, name):
    assert name in _eo_metrics()
    spec = load("layer_metrics", f"{name}.json")
    assert spec["params"] == {"span": span} and spec["layer"] == "recovery"
    reader = importlib.import_module(f"layer_metrics.readers.{spec['reader']}")
    obs = {"profile_spans": {span: (10.0, 200)}, "window_s": 50.0,
           "window_workers": 4}
    assert reader.read(obs, spec["params"]) == pytest.approx(5.0)
    assert reader.read(dict(obs, profile_spans={}), spec["params"]) is None


def test_every_eo_metric_names_the_one_cell_and_has_its_file():
    names = _eo_metrics()
    assert len(names) == 20
    for name in names:
        assert name.endswith(".eo")
        spec = load("layer_metrics", f"{name}.json")
        assert spec["name"] == name and spec["moves"] == "throughput_eps"
