"""The launch record and the three-way node split (ISSUE 24):

* a launch's ship phases share one id, in order, with ids unique across
  shards, and the same id reaches ``obs/trace``'s launch child spans;
* ``NodeStats`` splits a node's life into self / blocked / idle, in both
  receive loops, and times a source and the stages fused into it;
* the same boundaries read the thread's CPU clock (ISSUE 36): time in
  service and off the CPU -- a sleep, the interpreter lock -- shows as
  self minus self-CPU, per node, per fused stage and per span;
* a graph writes its own run's launch records, not an earlier graph's;
* with profiling off and no trace dir nothing is annotated, kept or written,
  and no CPU clock is read;
* every step family's executable carries the family's name.
"""

import json
import os
import resource
import sys
import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from windflow_tpu import MultiPipe, RecoveryPolicy
from windflow_tpu.core.tuples import Schema
from windflow_tpu.core.windows import WindowSpec, WinType
from windflow_tpu.obs.trace import TracePolicy
from windflow_tpu.ops import resident
from windflow_tpu.ops.functions import MultiReducer, Reducer
from windflow_tpu.patterns.basic import Map, Sink, Source
from windflow_tpu.patterns.native_core import NativeResidentCore
from windflow_tpu.patterns.win_seq_tpu import WinSeqTPU
from windflow_tpu.runtime.engine import Dataflow
from windflow_tpu.runtime.node import Node, SourceNode
from windflow_tpu.utils import profile, tracing

SCHEMA = Schema(value=np.int64)
LAUNCH_PHASES = ("launch_take", "device_put", "dispatch", "harvest_wait",
                 "harvest_finalize")


@pytest.fixture(autouse=True)
def _profile_state(monkeypatch):
    monkeypatch.delenv("WF_LOG_DIR", raising=False)
    monkeypatch.delenv("WF_PROFILE", raising=False)
    profile.disable()
    profile.reset()
    yield
    profile.auto()
    profile.reset()


def _rows(n, n_keys=8):
    ids = np.arange(n)
    b = np.zeros(n, dtype=SCHEMA.dtype())
    b["key"] = ids % n_keys
    b["id"] = ids // n_keys
    b["ts"] = ids // n_keys
    b["value"] = ids % 7
    return b


def _drive_core(shards, overlap, reducer=None):
    core = NativeResidentCore(WindowSpec(16, 8, WinType.CB),
                              reducer or Reducer("sum"), batch_len=64,
                              flush_rows=512, shards=shards, overlap=overlap)
    rows = _rows(8192)
    out = [core.process(rows[i:i + 1024]) for i in range(0, len(rows), 1024)]
    out.append(core.flush())
    core._stop_worker()
    return np.concatenate(out)


# ------------------------------------------------------------ launch record

@pytest.mark.parametrize("shards,overlap", [(1, True), (2, True),
                                            (1, False), (2, False)])
def test_launch_phases_share_one_id_in_order(shards, overlap):
    profile.enable()
    results = _drive_core(shards, overlap)
    assert len(results)
    by_launch = {}
    for phase, t0, t1, launch, shard, cause, extra, cpu in \
            profile.records():
        assert t1 >= t0 and cpu >= 0
        if launch is not None:
            by_launch.setdefault(launch, []).append(
                (phase, t0, t1, shard, cause, extra))
    assert by_launch
    for lid, spans in by_launch.items():
        phases = [s[0] for s in spans]
        assert sorted(phases) == sorted(LAUNCH_PHASES), (lid, phases)
        at = {s[0]: s for s in spans}
        # take <= put <= dispatch <= harvest <= finalize, each ending
        # before the next begins
        for a, b in zip(LAUNCH_PHASES, LAUNCH_PHASES[1:]):
            assert at[a][2] <= at[b][1], (lid, a, b)
        assert len({s[3] for s in spans}) == 1          # one ship thread
        cause = at["launch_take"][4]
        assert cause is not None and cause < lid        # fed before taken
        assert at["harvest_wait"][5]["ready"] in (True, False)
        live = at["launch_take"][5]
        # a launch may carry windows only (an EOS flush), so no live row
        assert 0 <= live["rows_live"] <= live["rows_shipped"]
    assert {spans[0][3] for spans in by_launch.values()} \
        == set(range(shards))                           # every shard shipped
    counters = profile.counters()
    assert counters["launches"] == len(by_launch)
    assert counters["launches_ready_at_poll"] <= counters["launches"]
    assert 0 < counters["rows_live"] <= counters["rows_shipped"]
    assert counters["rows_live"] == 8192                # every row, once
    causes = [c for p, _a, _b, _l, _s, c, *_ in profile.records()
              if p == "native_bookkeeping"]
    assert len(causes) == len(set(causes)) == 8


def test_multi_field_launch_counts_live_rows_per_field():
    profile.enable()
    schema = Schema(a=np.int64, b=np.int64)
    n = 2048
    ids = np.arange(n)
    rows = np.zeros(n, dtype=schema.dtype())
    rows["key"], rows["id"], rows["ts"] = ids % 4, ids // 4, ids // 4
    rows["a"], rows["b"] = ids % 5, ids % 3
    core = NativeResidentCore(
        WindowSpec(16, 16, WinType.CB),
        MultiReducer(Reducer("sum", "a", out_field="sa"),
                     Reducer("max", "b", out_field="mb")),
        batch_len=64, flush_rows=256, overlap=False)
    core.process(rows)
    core.flush()
    assert profile.counters()["rows_live"] == 2 * n


def test_service_is_computed_from_the_spans_own_stamps(monkeypatch):
    """resident reads the clock once per launch boundary: with profiling on
    the launch service is (harvest_wait end) - (dispatch end), exactly."""
    profile.enable()
    seen = []
    real = resident.ResidentWindowExecutor._note_service

    def spy(self, dt_ns, ready, how):
        seen.append(dt_ns)
        return real(self, dt_ns, ready, how)

    monkeypatch.setattr(resident.ResidentWindowExecutor, "_note_service", spy)
    _drive_core(1, False)
    at = {}
    for phase, t0, t1, launch, *_ in profile.records():
        if launch is not None:
            at.setdefault(launch, {})[phase] = (t0, t1)
    want = sorted(p["harvest_wait"][1] - p["dispatch"][1]
                  for p in at.values())
    assert sorted(seen) == want


def test_trace_launch_children_carry_the_launch_id():
    """obs/trace's recorder gets the launch id with the span it already
    got: launch child spans of a traced hop name their launch."""
    class Src(SourceNode):
        def generate(self):
            for i in range(3):
                self.emit(np.arange(4, dtype=np.int64) + i)

    class Mid(Node):
        def svc(self, batch, channel=0):
            with profile.span("dispatch", launch=profile.next_id(), shard=0):
                pass
            with profile.span("native_bookkeeping"):
                pass
            self.emit(batch)

    class Snk(Node):
        def svc(self, batch, channel=0):
            pass

    import warnings
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        df = Dataflow("tr", trace=TracePolicy(sample_rate=1.0))
    s, m, k = df.add(Src("src")), df.add(Mid("mid")), df.add(Snk("snk"))
    df.connect(s, m)
    df.connect(m, k)
    df.run_and_wait_end()
    launches = [r for r in df.tracer.recent if r["kind"] == "launch"]
    named = [r for r in launches if r["phase"] == "dispatch"]
    plain = [r for r in launches if r["phase"] == "native_bookkeeping"]
    assert len(named) == len(plain) == 3
    assert len({r["launch"] for r in named}) == 3
    assert all("launch" not in r for r in plain)
    assert not profile.records()           # profiling itself stayed off


# ------------------------------------------------- self / blocked / idle

class _Src(SourceNode):
    def generate(self):
        for i in range(30):
            self.emit(np.arange(8, dtype=np.int64) + i)


class _Mid(Node):
    recoverable = True

    def svc(self, batch, channel=0):
        self.emit(batch * 2)


class _SlowSink(Node):
    recoverable = True

    def svc(self, batch, channel=0):
        time.sleep(0.01)


def _node_logs(d):
    """``{node name: its .log}`` of a trace dir."""
    logs = {}
    for fn in os.listdir(d):
        if fn.endswith(".log"):
            with open(os.path.join(d, fn)) as f:
                logs[fn.split("_", 2)[2][:-4]] = json.load(f)
    return logs


def _three_nodes(tmp_path, supervised, trace_dir=True):
    df = Dataflow("split", capacity=1,
                  trace_dir=str(tmp_path / "log") if trace_dir else None,
                  recovery=RecoveryPolicy() if supervised else None)
    s, m, k = df.add(_Src("src")), df.add(_Mid("mid")), \
        df.add(_SlowSink("snk"))
    df.connect(s, m)
    df.connect(m, k)
    df.run_and_wait_end()
    return df


@pytest.mark.parametrize("supervised", [False, True],
                         ids=["run_node", "run_supervised"])
def test_self_blocked_idle_add_up_and_blocked_shows_before_the_slow_node(
        tmp_path, supervised):
    _three_nodes(tmp_path, supervised)
    logs = _node_logs(tmp_path / "log")
    assert set(logs) == {"src", "mid", "snk"}
    for name, log in logs.items():
        alive = log["alive_sec"] * 1e3
        parts = (log["self_ms_total"] + log["blocked_ms_total"]
                 + log["idle_ms_total"])
        assert abs(parts - alive) <= 0.02 * alive + 2.0, (name, log)
        assert "avg_interdeparture_us" not in log
    mid, snk, src = logs["mid"], logs["snk"], logs["src"]
    # the sink is slow: it is busy itself, and the node BEFORE it is
    # blocked on the sink's inbox most of its life
    assert snk["blocked_ms_total"] == 0.0
    assert snk["self_ms_total"] > 0.8 * snk["alive_sec"] * 1e3
    assert mid["blocked_ms_total"] > 0.5 * mid["alive_sec"] * 1e3
    assert mid["blocked_max_inbox"] == "snk"
    assert mid["blocked_max_ms"] >= 5.0
    assert mid["self_ms_total"] < 0.2 * mid["alive_sec"] * 1e3
    # and the source behind it is blocked on the middle node's inbox
    assert src["blocked_max_inbox"] == "mid"
    assert src["blocked_ms_total"] > 0.5 * src["alive_sec"] * 1e3
    assert src["rcv_batches"] == 0


def _ten_batches():
    ids = np.arange(64)
    batches = []
    for i in range(10):
        b = np.zeros(64, dtype=SCHEMA.dtype())
        b["key"], b["id"], b["ts"] = ids % 2, ids + 64 * i, ids + 64 * i
        batches.append(b)
    return batches


def test_source_fused_with_a_map_reports_the_maps_time(tmp_path):
    def slow_double(batch):
        time.sleep(0.004)
        batch["value"] *= 2

    batches = _ten_batches()
    d = str(tmp_path / "log")
    (MultiPipe("fused", trace_dir=d)
     .add_source(Source(batches=batches, schema=SCHEMA, name="src"))
     .chain(Map(slow_double, vectorized=True, name="dbl"))
     .add_sink(Sink(lambda r: None, vectorized=True))
     .run_and_wait_end())
    logs = [json.load(open(os.path.join(d, fn))) for fn in os.listdir(d)
            if fn.endswith(".log")]
    src = next(v for v in logs if "src" in v["node"] and "dbl" in v["node"])
    fused = src["fused_svc_ms"]
    assert list(fused) == ["dbl.0"]
    assert fused["dbl.0"] >= 10 * 4.0 * 0.9
    # the Map's own time: the puts it made on the sink's inbox are blocked
    # time, not the Map's
    assert fused["dbl.0"] + src["blocked_ms_total"] \
        <= src["svc_time_ms_total"] + 1.0


# ------------------------------------------------- the thread's CPU clock

def _off_cpu_ms(log):
    return log["self_ms_total"] - log["self_cpu_ms_total"]


@pytest.mark.parametrize("supervised", [False, True],
                         ids=["run_node", "run_supervised"])
def test_self_cpu_lies_within_self_and_the_split_still_adds_up(tmp_path,
                                                               supervised):
    _three_nodes(tmp_path, supervised)
    logs = _node_logs(tmp_path / "log")
    for name, log in logs.items():
        alive = log["alive_sec"] * 1e3
        assert 0 <= log["self_cpu_ms_total"] \
            <= 1.02 * log["self_ms_total"] + 1.0, (name, log)
        parts = (log["self_ms_total"] + log["blocked_ms_total"]
                 + log["idle_ms_total"])
        assert abs(parts - alive) <= 0.02 * alive + 2.0, (name, log)
        # a wait burns next to no CPU
        assert 0 <= log["wait_cpu_ms_total"] <= 0.1 * alive + 1.0, (name, log)
        assert log["ctx_voluntary"] >= 0 and log["ctx_involuntary"] >= 0
    # the sink sleeps 10 ms a batch: in service, off the CPU, and each
    # sleep a switch it made of its own accord
    snk = logs["snk"]
    assert _off_cpu_ms(snk) >= 0.8 * snk["self_ms_total"] >= 0.8 * 30 * 10.0
    assert snk["ctx_voluntary"] >= 30


def _burn(cpu_s):
    """Spin until this thread has had `cpu_s` seconds of CPU: however many
    others want this machine's cores, that much is on the CPU clock."""
    end = time.thread_time() + cpu_s
    while time.thread_time() < end:
        pass


class _Work(Node):
    """A sink that sleeps, spins, or does Python work between calls that
    let go of the interpreter lock."""

    def __init__(self, name, how):
        super().__init__(name)
        self.how = how

    def svc(self, batch, channel=0):
        if self.how == "sleep":
            time.sleep(0.05)
        elif self.how == "spin":
            _burn(0.05)
        else:
            for _ in range(10):
                sum(range(50000))
                time.sleep(0)       # lets go of the lock, wants it back


class _Few(SourceNode):
    def generate(self):
        for i in range(5):
            self.emit(np.arange(8, dtype=np.int64) + i)


def _work_log(d, how):
    df = Dataflow("work", trace_dir=str(d))
    df.connect(df.add(_Few("src")), df.add(_Work("work", how)))
    df.run_and_wait_end()
    return _node_logs(d)["work"]


@pytest.mark.parametrize("how", ["sleep", "spin"])
def test_a_sleep_reads_as_off_cpu_and_a_spin_as_none(tmp_path, how):
    log = _work_log(tmp_path, how)
    assert log["self_ms_total"] >= 5 * 50.0 * 0.95
    if how == "sleep":
        assert _off_cpu_ms(log) >= 0.9 * 5 * 50.0
        assert log["self_cpu_ms_total"] <= 0.1 * log["self_ms_total"]
    else:
        # every millisecond it burnt is on the node's CPU clock; what its
        # wall clock shows beyond them is this machine's other work (no
        # bound: the suite runs six processes wide)
        assert 0.95 * 5 * 50.0 <= log["self_cpu_ms_total"] \
            <= 1.02 * log["self_ms_total"]


def test_python_work_beside_a_lock_holder_reads_off_cpu(tmp_path):
    """A thread in a pure-Python loop gives the interpreter lock up only
    when the switch interval forces it: a node that lets go of the lock
    waits that long to get it back, inside its svc and off the CPU."""
    alone = _work_log(tmp_path / "alone", "python")
    stop = threading.Event()

    def hold():
        n = 0
        while not stop.is_set():
            n += 1

    holder = threading.Thread(target=hold, daemon=True)
    interval = sys.getswitchinterval()
    sys.setswitchinterval(0.02)
    holder.start()
    try:
        beside = _work_log(tmp_path / "beside", "python")
    finally:
        stop.set()
        sys.setswitchinterval(interval)
        holder.join(timeout=10)
    assert not holder.is_alive()
    # the same work on the CPU both times; beside the holder, letting go
    # of the lock (50 times) costs the node a switch interval more often
    # than not
    assert _off_cpu_ms(beside) >= 10 * 20.0
    assert _off_cpu_ms(beside) >= 3 * _off_cpu_ms(alone)
    assert _off_cpu_ms(beside) >= 0.8 * beside["self_ms_total"]
    # and each of those waits is a switch of the node's own accord
    assert beside["ctx_voluntary"] >= 50


def test_fused_stages_cpu_lies_within_the_chains(tmp_path):
    def sleepy(batch):
        time.sleep(0.004)

    def busy(batch):
        _burn(0.004)

    batches = _ten_batches()
    d = str(tmp_path / "log")
    (MultiPipe("fused", trace_dir=d)
     .add_source(Source(batches=batches, schema=SCHEMA, name="src"))
     .chain(Map(sleepy, vectorized=True, name="sleepy"))
     .chain(Map(busy, vectorized=True, name="busy"))
     .add_sink(Sink(lambda r: None, vectorized=True))
     .run_and_wait_end())
    src = next(v for v in _node_logs(d).values() if v["fused_svc_ms"])
    wall, cpu = src["fused_svc_ms"], src["fused_cpu_ms"]
    assert sorted(cpu) == sorted(wall) == ["busy.0", "sleepy.0"]
    for stage in wall:
        assert 0 <= cpu[stage] <= 1.02 * wall[stage] + 0.5
    # the chain's CPU: the source thread's, its blocked puts taken out
    assert sum(cpu.values()) <= src["self_cpu_ms_total"] + 0.5
    # each stage's own: the sleeper's sleep is off the CPU and not booked
    # to the stage below it, whose spin is on it
    assert wall["sleepy.0"] - cpu["sleepy.0"] >= 10 * 4.0 * 0.9
    assert cpu["sleepy.0"] <= 0.25 * wall["sleepy.0"]
    assert cpu["busy.0"] >= 10 * 4.0 * 0.95


@pytest.mark.parametrize("how", ["sleep", "spin"])
def test_a_span_record_carries_its_cpu_time(how):
    profile.enable()
    with profile.span("device_put", launch=profile.next_id(), shard=0):
        _Work("w", how).svc(None)
    profile.record("reorder_merge", 0.001)      # timed inside a native call
    rec, = profile.records()
    wall, cpu = rec[2] - rec[1], rec[7]
    assert 0 <= cpu <= 1.02 * wall
    if how == "sleep":
        assert cpu <= 0.1 * wall
    else:
        assert cpu >= 0.95 * 50e6
    assert profile.report()["device_put"][1] == 1   # report()'s shape stays


# ---------------------------------------------- where the CPU clock is dear

@pytest.mark.parametrize("read_us, every", [(0, 1), (5, 17)],
                         ids=["a kernel's own", "a sandbox's"])
def test_the_clocks_cost_sets_how_often_it_is_read(monkeypatch, read_us,
                                                   every):
    real = time.thread_time_ns

    def dear():
        end = time.perf_counter_ns() + read_us * 1000
        while time.perf_counter_ns() < end:
            pass
        return real()

    monkeypatch.setattr(time, "thread_time_ns", dear)
    monkeypatch.setattr(tracing, "_CPU_EVERY", None)
    assert tracing.cpu_every() == every
    assert tracing.cpu_every() == every         # measured once


@pytest.mark.parametrize("supervised", [False, True],
                         ids=["run_node", "run_supervised"])
def test_a_dear_cpu_clock_is_read_on_every_17th_wait_and_scaled_up(
        tmp_path, monkeypatch, supervised):
    reads = []
    real = time.thread_time_ns

    def counted():
        reads.append(threading.current_thread().name)
        return real()

    monkeypatch.setattr(tracing, "_CPU_EVERY", 17)
    monkeypatch.setattr(time, "thread_time_ns", counted)
    _three_nodes(tmp_path, supervised)
    logs = _node_logs(tmp_path / "log")
    # a node's run at its two ends, and every 17th of its gets and puts
    # (src 30 puts; mid 31 gets, 30 puts; snk 31 gets), the first of them
    for name, pieces in (("src", 30), ("mid", 61), ("snk", 31)):
        n = sum(r.endswith("/" + name) for r in reads)
        assert n == 2 + 2 * (1 + (pieces - 1) // 17), (name, n)
    # the whole run is on the clock, so the split is as exact as before
    snk, mid = logs["snk"], logs["mid"]
    assert _off_cpu_ms(snk) >= 0.8 * snk["self_ms_total"] >= 0.8 * 30 * 10.0
    # (the waits' CPU is an estimate from two to four of them here: it can
    # come out a shade over what the run had in all)
    for log in logs.values():
        alive = log["alive_sec"] * 1e3
        assert -0.02 * alive - 1.0 <= log["self_cpu_ms_total"] \
            <= 1.02 * log["self_ms_total"] + 1.0, log
        assert 0 <= log["wait_cpu_ms_total"] <= 0.1 * alive + 1.0, log
    # mid is blocked behind the slow sink: the puts that were followed burnt
    # next to nothing, and the scaled-up total says so of all of them
    assert mid["blocked_ms_total"] >= 200.0
    assert mid["wait_cpu_ms_total"] <= 0.1 * mid["blocked_ms_total"]


def test_a_dear_cpu_clock_follows_every_17th_fused_put(tmp_path,
                                                       monkeypatch):
    def sleepy(batch):
        time.sleep(0.004)

    def busy(batch):
        _burn(0.002)

    monkeypatch.setattr(tracing, "_CPU_EVERY", 17)
    d = str(tmp_path / "log")
    (MultiPipe("fused", trace_dir=d)
     .add_source(Source(batches=_ten_batches() * 4, schema=SCHEMA,
                        name="src"))
     .chain(Map(sleepy, vectorized=True, name="sleepy"))
     .chain(Map(busy, vectorized=True, name="busy"))
     .add_sink(Sink(lambda r: None, vectorized=True))
     .run_and_wait_end())
    src = next(v for v in _node_logs(d).values() if v["fused_svc_ms"])
    # three of forty calls were followed, the stage below with them, and
    # scaled up to each stage's wall time
    wall, cpu = src["fused_svc_ms"], src["fused_cpu_ms"]
    assert wall["sleepy.0"] >= 40 * 4.0 and wall["busy.0"] >= 40 * 2.0
    for stage in wall:
        assert 0 <= cpu[stage] <= 1.02 * wall[stage] + 0.5
    assert cpu["sleepy.0"] <= 0.25 * wall["sleepy.0"]
    assert cpu["busy.0"] >= 3 * cpu["sleepy.0"]
    # the source's own run is read whole: the forty spins are in it
    assert src["self_cpu_ms_total"] >= 0.95 * 40 * 2.0


def test_a_dear_cpu_clock_is_on_every_17th_span_of_a_phase(tmp_path,
                                                           monkeypatch):
    monkeypatch.setattr(tracing, "_CPU_EVERY", 17)
    profile.enable()
    for i in range(40):
        with profile.span("device_put", launch=i, shard=0):
            pass
        if i % 2:
            with profile.span("dispatch", launch=i, shard=0):
                pass
    followed = {}
    for phase, _t0, _t1, launch, *_rest, cpu in profile.records():
        if cpu is not None:
            assert cpu >= 0
            followed.setdefault(phase, []).append(launch)
    assert followed == {"device_put": [0, 17, 34], "dispatch": [1, 35]}
    path = str(tmp_path / "launches.jsonl")
    assert profile.write_records(path) == 60
    with open(path) as f:
        lines = [json.loads(ln) for ln in f]
    assert sum("cpu_ns" in ln for ln in lines) == 5
    assert all(ln["t1_ns"] >= ln["t0_ns"] for ln in lines)


# --------------------------------------------- a graph's own launch records

def test_a_plain_graph_writes_no_earlier_graphs_launch_records(tmp_path):
    """The ring is the process's: a graph with a trace dir wrote whatever
    spans an earlier graph had left there."""
    profile.enable()
    with profile.span("dispatch", launch=profile.next_id(), shard=0):
        pass
    profile.disable()
    assert len(profile.records()) == 1
    df = Dataflow("plain", trace_dir=str(tmp_path))
    df.connect(df.add(_Src("src")), df.add(_SlowSink("snk")))
    df.run_and_wait_end()
    assert sorted(os.listdir(tmp_path)) == ["plain_00_src.log",
                                            "plain_01_snk.log"]


# ------------------------------------------------------------ switched off

@pytest.mark.parametrize("supervised", [False, True],
                         ids=["run_node", "run_supervised"])
def test_off_means_no_annotation_no_record_no_file(tmp_path, monkeypatch,
                                                   supervised):
    def no_annotation(*a, **k):
        raise AssertionError("TraceAnnotation built with profiling off")

    monkeypatch.setattr(profile, "_annotation", no_annotation)
    monkeypatch.chdir(tmp_path)
    df = _three_nodes(tmp_path, supervised, trace_dir=False)
    assert all(n.stats is None for n in df.nodes)
    _drive_core(1, True)
    assert profile.records() == [] and profile.report() == {}
    assert profile.counters() == {}
    assert os.listdir(tmp_path) == []


@pytest.mark.parametrize("supervised", [False, True],
                         ids=["run_node", "run_supervised"])
def test_off_reads_no_cpu_clock_and_no_switch_count(tmp_path, monkeypatch,
                                                    supervised):
    def never(*a, **k):
        raise AssertionError("a CPU clock read with stats and profiling off")

    monkeypatch.setattr(time, "thread_time_ns", never)
    monkeypatch.setattr(resource, "getrusage", never)
    df = _three_nodes(tmp_path, supervised, trace_dir=False)
    assert all(n.stats is None for n in df.nodes)
    # a window pipeline: the engine, a worker's bookkeeping spans and its
    # ship thread's phases
    got = []
    rows = _rows(8192)
    (MultiPipe("win", recovery=RecoveryPolicy() if supervised else None)
     .add_source(Source(batches=[rows[i:i + 1024]
                                 for i in range(0, len(rows), 1024)],
                        schema=SCHEMA))
     .add(WinSeqTPU(Reducer("sum"), 16, 8, WinType.CB, batch_len=64,
                    flush_rows=512))
     .add_sink(Sink(got.append, vectorized=True))
     .run_and_wait_end())
    assert sum(len(g) for g in got if g is not None) >= 8192 // 8 - 8
    assert profile.records() == []


def test_launch_file_written_only_with_profiling_and_a_trace_dir(tmp_path):
    class CoreNode(Node):
        def svc(self, batch, channel=0):
            with profile.span("dispatch", launch=profile.next_id(), shard=3,
                              cause=1) as sp:
                sp.extra = {"ready": True}

    def run(d):
        df = Dataflow("lf", trace_dir=d)
        s, c = df.add(_Src("src")), df.add(CoreNode("core"))
        df.connect(s, c)
        df.run_and_wait_end()

    off = str(tmp_path / "off")
    run(off)
    assert "launches.jsonl" not in os.listdir(off)
    profile.enable()
    on = str(tmp_path / "on")
    run(on)
    with open(os.path.join(on, "launches.jsonl")) as f:
        lines = [json.loads(ln) for ln in f]
    assert len(lines) == 30
    assert all(ln["phase"] == "dispatch" and ln["shard"] == 3
               and ln["cause"] == 1 and ln["ready"] is True
               and ln["t1_ns"] >= ln["t0_ns"]
               and 0 <= ln["cpu_ns"] <= ln["t1_ns"] - ln["t0_ns"] + 1e5
               for ln in lines)
    assert len({ln["launch"] for ln in lines}) == 30


# ----------------------------------------------------------- family names

KP, CAP, RB, C, B = 8, 256, 32, 8, 16
I8, I32 = np.dtype(np.int8).str, np.dtype(np.int32).str


def _S(shape, dt):
    return jax.ShapeDtypeStruct(shape, dt)


def _reg_key(cap, rb):
    return resident.StepKey("regular", resident._ANY_DEVICE, "sum", cap, rb,
                            C, KP, I8, I32, slide=4)


def _family(name):
    k = _S((KP,), jnp.int32)
    b = _S((B,), jnp.int32)
    ring, blk = _S((KP, CAP), jnp.int32), _S((KP, RB), jnp.int8)
    if name == "wf_step_regular":
        return resident._make_regular_step(_reg_key(CAP, RB)), \
            (ring, blk, k, k, k, k)
    one = resident._ANY_DEVICE
    if name == "wf_step_append_eval":
        fn = resident._make_step(resident.StepKey(
            "append_eval", one, ("max",), CAP, RB, B, KP, I8, I32, 16))
        return fn, (ring, blk, k, b, b, b)
    multi = resident.StepKey(
        "multi", one, (("sum", "a"), ("max", "b")), CAP, RB, B, KP, (I8, I8),
        (I32, I32), 16, fields=("a", "b"))
    if name == "wf_step_multi":
        fn = resident._make_multi_step(multi, None)
        return fn, ((ring, ring), (blk, blk), k, b, b, b, b, b)
    if name == "wf_step_argext":
        fn = resident._make_argext_step(multi._replace(
            family="argext", stats=(("argmax", "a"), ("sum", "b")), pad=0,
            eb=256))
        return fn, ((ring, ring), (blk, blk), k, k, b, b, b)
    from jax.sharding import Mesh
    on = resident._OnMesh(Mesh(np.array(jax.devices()[:4]), ("kf",)), "kf")
    d = _S((4, B), jnp.int32)
    if name == "wf_step_regular_mesh":
        fn = resident._make_regular_step(_reg_key(CAP, RB)._replace(place=on))
        return fn, (ring, blk, k, k, k, k)
    if name == "wf_step_append_eval_mesh":
        fn = resident._make_step(resident.StepKey(
            "append_eval", on, ("max",), CAP, RB, B, KP, I8, I32, 16))
        return fn, (ring, blk, k, d, d, d)
    assert name == "wf_step_multi_mesh"
    fn = resident._make_multi_step(multi._replace(place=on), None)
    return fn, ((ring, ring), (blk, blk), k, d, d, d, d, d)


@pytest.mark.parametrize("name", [
    "wf_step_regular", "wf_step_append_eval", "wf_step_multi",
    "wf_step_argext", "wf_step_regular_mesh", "wf_step_append_eval_mesh",
    "wf_step_multi_mesh"])
def test_step_executable_is_named_by_its_family(name):
    fn, args = _family(name)
    text = fn.lower(*args).as_text()
    assert f"module @jit_{name} " in text, text[:120]


def test_prewarm_ladder_goes_through_the_named_factories():
    """The ladder warms what the window uses: its siblings come from the
    same factories, so they carry the family's name too."""
    key = _reg_key(1024, 16)
    saved = dict(resident._STEP_CACHE)
    saved_warm = set(resident._PREWARMED)
    try:
        resident._STEP_CACHE.clear()
        resident._STEP_CACHE[key] = resident._make_regular_step(key)
        assert resident.prewarm_regular_ladder(mults=(2,)) == 2
        for k, fn in resident._STEP_CACHE.items():
            assert fn.__name__ == "wf_step_regular", k
    finally:
        resident._STEP_CACHE.clear()
        resident._STEP_CACHE.update(saved)
        resident._PREWARMED.clear()
        resident._PREWARMED.update(saved_warm)
