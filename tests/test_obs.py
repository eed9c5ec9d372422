"""Observability-layer tests (windflow_tpu/obs, docs/OBSERVABILITY.md):
the registry/event-log primitives, the background sampler's file output
validated line-by-line against the documented schema (obs_schema.py —
the same validator the slow soak slice uses), the single-branch disabled
contract, the wire telemetry, the Prometheus exposition, wf_top's
renderer, and the profile/latency satellite knobs."""

import importlib.util
import json
import os
import threading
import time

import numpy as np
import pytest

from obs_schema import validate_event, validate_file, validate_sample
from windflow_tpu import (EventLog, Map_Builder, MetricsRegistry, MultiPipe,
                          Sink_Builder, Source_Builder)
from windflow_tpu.core.tuples import Schema, batch_from_columns
from windflow_tpu.obs import expo
from windflow_tpu.patterns.basic import Map, Sink, Source
from windflow_tpu.runtime.engine import Dataflow
from windflow_tpu.runtime.farm import build_pipeline
from windflow_tpu.runtime.overload import OverloadPolicy

SCHEMA = Schema(value=np.int64)


def make_batches(n=40, rows=10, poison_at=()):
    out = []
    for i in range(n):
        vals = np.full(rows, i, dtype=np.int64)
        if i in poison_at:
            vals[0] = -1
        out.append(batch_from_columns(
            SCHEMA, key=np.zeros(rows), id=np.arange(rows),
            ts=np.arange(rows), value=vals))
    return out


def _load_script(name):
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "scripts", f"{name}.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


# ------------------------------------------------------------ primitives

def test_registry_counter_gauge_histogram():
    reg = MetricsRegistry()
    reg.counter("c").inc()
    reg.counter("c").inc(4)
    reg.gauge("g").set(2.5)
    h = reg.histogram("h", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.5, 5.0):
        h.observe(v)
    snap = reg.snapshot()
    assert snap["counters"]["c"] == 5
    assert snap["gauges"]["g"] == 2.5
    hs = snap["histograms"]["h"]
    assert hs["count"] == 4 and hs["sum"] == pytest.approx(5.555)
    # cumulative prometheus-style buckets; 5.0 only in implicit +Inf
    assert list(hs["buckets"].values()) == [1, 2, 3]
    # same name, different kind: loud error, not silent shadowing
    with pytest.raises(TypeError, match="already registered"):
        reg.gauge("c")


def test_registry_thread_safety():
    reg = MetricsRegistry()
    c = reg.counter("hits")

    def spin():
        for _ in range(10_000):
            c.inc()

    threads = [threading.Thread(target=spin) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert c.value == 40_000


def test_event_log_ring_file_and_vocabulary(tmp_path):
    path = str(tmp_path / "sub" / "events.jsonl")
    log = EventLog(path, keep=3)
    assert not os.path.exists(path)     # lazy: nothing until first emit
    for i in range(5):
        log.emit("eos", node="n", channel=i)
    log.close()
    assert [e["channel"] for e in log.recent] == [2, 3, 4]  # bounded ring
    assert validate_file(path, validate_event) == 5
    with pytest.raises(ValueError, match="unknown event"):
        log.emit("made_up_event")


# ------------------------------------------------------- engine sampling

def build_observed(tmp_path, sink_delay=0.002, n=40, sample_period=0.005,
                   policy=None, metrics=None):
    d = str(tmp_path / "obs")

    def consume(rows):
        if rows is not None and len(rows) and sink_delay:
            time.sleep(sink_delay)

    df = Dataflow("obs", capacity=4, trace_dir=d, overload=policy,
                  metrics=metrics, sample_period=sample_period)
    build_pipeline(df, [Source(batches=make_batches(n), schema=SCHEMA),
                        Sink(consume, vectorized=True)])
    return df, d


def test_smoke_metrics_and_events_schema(tmp_path):
    """The tier-1 observability smoke test (ISSUE 4 satellite): a tiny
    dataflow with sample_period set writes metrics.jsonl + events.jsonl
    whose EVERY line satisfies the documented schema, with live samples
    (not just the final flush) present."""
    df, d = build_observed(tmp_path)
    df.run_and_wait_end()
    n_samples = validate_file(os.path.join(d, "metrics.jsonl"),
                              validate_sample)
    n_events = validate_file(os.path.join(d, "events.jsonl"),
                             validate_event)
    assert n_samples >= 2       # the t=0 sample plus at least the flush
    assert n_events >= 2 + 2 * df.cardinality()  # start/stop + per node
    lines = [json.loads(line)
             for line in open(os.path.join(d, "metrics.jsonl"))]
    assert [s["seq"] for s in lines] == list(range(len(lines)))
    # the sink's queue visibly backs up while running: live occupancy
    assert max(n["depth"] for s in lines for n in s["nodes"]) > 0
    assert max(n["hwm"] for s in lines for n in s["nodes"]) > 0
    kinds = {json.loads(line)["event"]
             for line in open(os.path.join(d, "events.jsonl"))}
    assert {"dataflow_start", "node_start", "eos", "node_stop",
            "dataflow_stop"} <= kinds


def test_observability_disabled_is_inert(tmp_path):
    """Knobs unset => no registry, no event log, no sampler thread, no
    metrics/events files, no inbox tracking — the seed contract."""
    d = str(tmp_path / "plain")
    df = Dataflow("plain", capacity=4, trace_dir=d)
    build_pipeline(df, [Source(batches=make_batches(8), schema=SCHEMA),
                        Sink(lambda r: None, vectorized=True)])
    assert df.metrics is None and df.events is None
    assert all(not ib._track for ib in df._inboxes.values())
    df.run_and_wait_end()
    assert df._sampler is None
    files = set(os.listdir(d))
    assert "metrics.jsonl" not in files and "events.jsonl" not in files
    assert len(files) == 2      # exactly the seed per-node .log files


def test_metrics_without_trace_dir_stays_in_memory(tmp_path, monkeypatch):
    monkeypatch.delenv("WF_LOG_DIR", raising=False)
    df = Dataflow("mem", capacity=4, metrics=True, sample_period=0.005)
    build_pipeline(df, [Source(batches=make_batches(10), schema=SCHEMA),
                        Sink(lambda r: None, vectorized=True)])
    df.run_and_wait_end()
    assert df.metrics is not None
    assert any(e["event"] == "dataflow_stop" for e in df.events.recent)
    assert not os.path.exists(str(tmp_path / "metrics.jsonl"))
    # NodeStats exist for live sampling even though nothing hit disk
    assert all(n.stats is not None for n in df.nodes)


def test_sample_period_env_hook(tmp_path, monkeypatch):
    d = str(tmp_path / "env")
    monkeypatch.setenv("WF_LOG_DIR", d)
    monkeypatch.setenv("WF_SAMPLE_PERIOD", "0.005")
    df = Dataflow("envobs", capacity=4)
    build_pipeline(df, [Source(batches=make_batches(10), schema=SCHEMA),
                        Sink(lambda r: None, vectorized=True)])
    df.run_and_wait_end()
    assert validate_file(os.path.join(d, "metrics.jsonl"),
                         validate_sample) >= 1
    monkeypatch.setenv("WF_SAMPLE_PERIOD", "not-a-number")
    with pytest.raises(ValueError):
        Dataflow("bad")
    monkeypatch.setenv("WF_SAMPLE_PERIOD", "-1")
    with pytest.raises(ValueError):
        Dataflow("bad")


def test_rich_functions_see_ctx_metrics():
    seen = []

    def bump(batch, ctx):
        ctx.metrics.counter("custom_rows").inc(len(batch))
        seen.append(ctx.metrics)

    pipe = (MultiPipe("rich", metrics=True)
            .add_source(Source_Builder().withBatches(make_batches(5))
                        .withSchema(SCHEMA).build())
            .add(Map_Builder(bump).withRich().vectorized().build())
            .add_sink(Sink_Builder(lambda r: None).vectorized().build()))
    pipe.run_and_wait_end()
    assert seen and all(m is pipe.metrics for m in seen)
    assert pipe.metrics.snapshot()["counters"]["custom_rows"] == 50


def test_ctx_metrics_survives_chain_fusion():
    """chain() fuses stages into one Comb thread; each fused stage keeps
    its own RuntimeContext, so the registry handle must be forwarded."""

    def bump(batch, ctx):
        ctx.metrics.counter("chained_rows").inc(len(batch))

    pipe = (MultiPipe("fused", metrics=True)
            .add_source(Source_Builder().withBatches(make_batches(4))
                        .withSchema(SCHEMA).build())
            .add(Map_Builder(lambda b: b).vectorized().build())
            .chain(Map_Builder(bump).withRich().vectorized().build())
            .add_sink(Sink_Builder(lambda r: None).vectorized().build()))
    pipe.run_and_wait_end()
    assert pipe.metrics.snapshot()["counters"]["chained_rows"] == 40


def test_multipipe_plumbing_and_union(tmp_path):
    reg = MetricsRegistry()
    p1 = (MultiPipe("a", metrics=reg, sample_period=0.5)
          .add_source(Source_Builder().withBatches(make_batches(3))
                      .withSchema(SCHEMA).build()))
    p2 = (MultiPipe("b", sample_period=0.25)
          .add_source(Source_Builder().withBatches(make_batches(3))
                      .withSchema(SCHEMA).build()))
    merged = MultiPipe.union(p1, p2, name="u")
    merged.add_sink(Sink_Builder(lambda r: None).vectorized().build())
    assert merged.sample_period == 0.25     # finest cadence wins
    merged.run_and_wait_end()
    assert merged.metrics is reg            # first configured registry


# ------------------------------------------------------------- exposition

def test_expo_registry_and_sample_formats():
    reg = MetricsRegistry()
    reg.counter("wire_bytes_sent").inc(128)
    reg.gauge("depth").set(3)
    reg.histogram("lat", buckets=(0.1, 1.0)).observe(0.05)
    txt = expo.render_registry(reg)
    assert "# TYPE wf_wire_bytes_sent counter" in txt
    assert "wf_wire_bytes_sent 128" in txt
    assert 'wf_lat_bucket{le="0.1"} 1' in txt
    assert "wf_lat_count 1" in txt
    sample = {"t": time.time(), "seq": 0, "dataflow": "df",
              "nodes": [{"node": "sink.0", "id": "df_01_sink.0",
                         "depth": 2, "hwm": 4, "shed": 7,
                         "quarantined": 0}],
              "dead_letters": 1, "counters": {"wire_frames_sent": 9},
              "gauges": {}, "histograms": {}}
    txt = expo.render_sample(sample)
    assert 'wf_node_inbox_depth{dataflow="df",node="sink.0"} 2' in txt
    assert 'wf_node_shed_total{dataflow="df",node="sink.0"} 7' in txt
    assert 'wf_dead_letters{dataflow="df"} 1' in txt
    assert "wf_wire_frames_sent 9" in txt


# ---------------------------------------------------------------- wf_top

def test_wf_top_renders_live_dir(tmp_path):
    df, d = build_observed(tmp_path)
    df.run_and_wait_end()
    wf_top = _load_script("wf_top")
    samples, _ = wf_top.read_samples(os.path.join(d, "metrics.jsonl"))
    assert len(samples) >= 2
    frame = wf_top.render(samples[-1], samples[-2],
                          wf_top.tail_events(os.path.join(d,
                                                          "events.jsonl")))
    assert "sink.0" in frame and "DEPTH" in frame and "SHED" in frame
    assert "dataflow=obs" in frame
    # --once exercises the CLI path end to end
    assert wf_top.main([d, "--once"]) == 0
    # --expo path renders the final sample
    assert wf_top.main([d, "--expo"]) == 0


# ------------------------------------------------------------- wire plane

def test_wire_telemetry_counters_conserved():
    from windflow_tpu.parallel.channel import RowReceiver, RowSender
    reg = MetricsRegistry()
    log = EventLog()
    recv = RowReceiver(n_senders=1, metrics=reg, events=log)
    got = []
    t = threading.Thread(target=lambda: got.extend(recv.batches()))
    t.start()
    snd = RowSender(recv.host, recv.port, metrics=reg, events=log)
    for lo in (0, 8):
        ids = np.arange(lo, lo + 8)
        snd.send(batch_from_columns(SCHEMA, key=np.zeros(8), id=ids,
                                    ts=ids, value=ids))
    snd.close()
    t.join(10)
    assert len(got) == 2
    c = reg.snapshot()["counters"]
    # dtype frame + 2 payload frames, byte-for-byte conserved
    assert c["wire_frames_sent"] == c["wire_frames_recv"] == 3
    assert c["wire_bytes_sent"] == c["wire_bytes_recv"] > 0


def test_wire_reconnect_events():
    import socket
    from windflow_tpu.parallel.channel import RowReceiver, RowSender
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    reg = MetricsRegistry()
    log = EventLog()
    out = {}

    def connect_late():
        out["snd"] = RowSender("127.0.0.1", port, connect_deadline=30,
                               metrics=reg, events=log)

    th = threading.Thread(target=connect_late)
    th.start()
    time.sleep(0.25)
    recv = RowReceiver(n_senders=1, port=port)
    th.join(30)
    out["snd"].close()
    recv.close()
    assert reg.snapshot()["counters"]["wire_connect_retries"] >= 1
    events = [e for e in log.recent if e["event"] == "reconnect_attempt"]
    assert events and events[0]["port"] == port
    for e in log.recent:
        validate_event(e)


# ----------------------------------------------------- profile satellite

def test_profile_toggles_without_reimport(monkeypatch):
    from windflow_tpu.utils import profile
    profile.auto()
    profile.reset()
    monkeypatch.delenv("WF_PROFILE", raising=False)
    with profile.span("phase"):
        pass
    assert profile.report() == {}       # env off => no accumulation
    monkeypatch.setenv("WF_PROFILE", "1")   # no re-import required
    with profile.span("phase"):
        pass
    profile.add("bytes", 7)
    assert profile.report()["phase"][1] == 1
    assert profile.counters()["bytes"] == 7
    profile.disable()                   # explicit pin beats the env
    with profile.span("phase"):
        pass
    assert profile.report()["phase"][1] == 1
    profile.enable()
    monkeypatch.delenv("WF_PROFILE", raising=False)
    with profile.span("phase"):
        pass
    assert profile.report()["phase"][1] == 2
    profile.auto()
    profile.reset()
    assert not profile.ENABLED


# ----------------------------------------------------- latency satellite

def test_latency_summarize_p50_and_n():
    from windflow_tpu.utils.latency import summarize
    s = summarize([np.arange(1, 101, dtype=np.float64)])
    assert s["n"] == 100
    assert s["p50"] == pytest.approx(50.5)
    assert set(s) == {"avg", "p50", "p95", "p99", "n"}
    assert summarize([]) == {}
    # the bench sinks splat these through unchanged names + new keys
    from windflow_tpu.apps.ysb import YSBSink
    sink = YSBSink(start_wall_us=0, now_us=lambda: 1000)
    sink(batch_from_columns(Schema(count=np.int64, lastUpdate=np.int64),
                            key=np.zeros(4), id=np.arange(4),
                            ts=np.arange(4), count=np.ones(4),
                            lastUpdate=np.arange(4)))
    m = sink.latency_summary_us()
    assert m["n_latency_samples"] == 4
    assert {"avg_latency_us", "p50_latency_us",
            "p95_latency_us", "p99_latency_us"} <= set(m)


def test_gather_counters_reach_the_log_and_metrics_jsonl(tmp_path):
    """The Filter's selectivity and the keyed emitter's split tally
    (docs/OBSERVABILITY.md) land where every other NodeStats counter
    does: the node's ``.log`` and the node's entry in ``metrics.jsonl``."""
    from windflow_tpu.patterns.basic import Filter

    d = str(tmp_path / "gather")
    rows = 10
    batches = [batch_from_columns(
        SCHEMA, key=np.arange(rows) + i, id=np.arange(rows),
        ts=np.arange(rows), value=np.arange(rows)) for i in range(12)]
    df = Dataflow("g", capacity=4, trace_dir=d, sample_period=0.005)
    build_pipeline(df, [Source(batches=batches, schema=SCHEMA),
                        Filter(lambda b: b["value"] % 5 != 0,
                               vectorized=True),
                        Map(lambda b: None, vectorized=True, parallelism=2,
                            keyed=True),
                        Sink(lambda r: None, vectorized=True)])
    df.run_and_wait_end()
    logs = [json.load(open(os.path.join(d, f)))
            for f in sorted(os.listdir(d)) if f.endswith(".log")]
    filt = [v for v in logs if "filter_rows_in" in v]
    assert len(filt) == 1
    assert filt[0]["filter_rows_in"] == 12 * rows
    assert filt[0]["filter_rows_out"] == 12 * (rows - 2)
    split = [v for v in logs if "split_batches" in v]
    assert len(split) == 1 and split[0]["split_batches"] == 12
    assert "single_dest_batches" not in split[0]      # never bumped
    validate_file(os.path.join(d, "metrics.jsonl"), validate_sample)
    last = [json.loads(line)
            for line in open(os.path.join(d, "metrics.jsonl"))][-1]
    by = {k: n[k] for n in last["nodes"] for k in
          ("filter_rows_in", "filter_rows_out", "split_batches") if k in n}
    assert by == {"filter_rows_in": 120, "filter_rows_out": 96,
                  "split_batches": 12}
