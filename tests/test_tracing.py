"""Tracing subsystem tests — the -DLOG_DIR instrumentation analog
(SURVEY.md §5): per-node counters written at svc_end when enabled, zero
files (and near-zero overhead branches) when disabled."""

import json
import os

import numpy as np

from windflow_tpu import (MultiPipe, Reducer, Schema, Sink_Builder,
                          Source_Builder, WinSeq_Builder,
                          batch_from_columns)

SCHEMA = Schema(value=np.int64)


def batches(n=100):
    ids = np.arange(n)
    return [batch_from_columns(SCHEMA, key=ids % 2, id=ids // 2,
                               ts=ids // 2, value=np.ones(n, dtype=np.int64))]


def build(trace_dir=None):
    return (MultiPipe("tr", trace_dir=trace_dir)
            .add_source(Source_Builder().withBatches(batches())
                        .withSchema(SCHEMA).build())
            .add(WinSeq_Builder(Reducer("sum")).withCBWindow(10, 10).build())
            .add_sink(Sink_Builder(lambda r: None).build()))


def test_trace_files_written(tmp_path):
    d = str(tmp_path / "log")
    build(trace_dir=d).run_and_wait_end()
    files = sorted(f for f in os.listdir(d) if f.endswith(".log"))
    assert len(files) == 3  # source, win_seq, sink
    logs = {f: json.load(open(os.path.join(d, f))) for f in files}
    win = next(v for v in logs.values() if "windows_fired" in v)
    assert win["rcv_batches"] == 1
    assert win["rcv_tuples"] == 100
    assert win["windows_fired"] == 10  # 2 keys x 5 tumbling windows
    assert win["avg_service_us_per_batch"] > 0
    sink = next(v for v in logs.values() if v["node"].endswith("sink.0"))
    assert sink["rcv_tuples"] == 10


def test_no_trace_files_by_default(tmp_path, monkeypatch):
    monkeypatch.delenv("WF_LOG_DIR", raising=False)
    build().run_and_wait_end()
    assert not os.path.exists(str(tmp_path / "log"))


def test_env_var_enables_tracing(tmp_path, monkeypatch):
    d = str(tmp_path / "envlog")
    monkeypatch.setenv("WF_LOG_DIR", d)
    build().run_and_wait_end()
    assert len([f for f in os.listdir(d) if f.endswith(".log")]) == 3


def test_snapshot_carries_robustness_counters():
    """NodeStats.snapshot() is the one view the end-of-run log, the live
    sampler, and wf_top all read: the robustness counters
    (docs/ROBUSTNESS.md) must surface there by their documented names."""
    from windflow_tpu.utils.tracing import NodeStats
    stats = NodeStats("df_00_check.0")
    stats.record_svc(100, 5_000)
    stats.record_shed(7)
    stats.record_quarantined()
    stats.record_quarantined()
    snap = stats.snapshot()
    assert snap["shed"] == 7
    assert snap["quarantined"] == 2
    assert snap["rcv_tuples"] == 100
    assert snap["node"] == "df_00_check.0"


def test_snapshot_is_live_mid_run():
    """snapshot() readable while the node is still running — the
    contract the background sampler (obs/sampler.py) relies on."""
    from windflow_tpu.utils.tracing import NodeStats
    stats = NodeStats("live")
    before = stats.snapshot()
    assert before["rcv_batches"] == 0
    stats.record_svc(10, 1_000)
    after = stats.snapshot()
    assert after["rcv_batches"] == 1
    assert after["alive_sec"] >= before["alive_sec"]
