"""The launch record and the three-way node split (ISSUE 24):

* a launch's ship phases share one id, in order, with ids unique across
  shards, and the same id reaches ``obs/trace``'s launch child spans;
* ``NodeStats`` splits a node's life into self / blocked / idle, in both
  receive loops, and times a source and the stages fused into it;
* with profiling off and no trace dir nothing is annotated, kept or written;
* every step family's executable carries the family's name.
"""

import json
import os
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
from windflow_tpu.runtime.engine import Dataflow
from windflow_tpu.runtime.node import Node, SourceNode
from windflow_tpu.utils import profile

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
    for phase, t0, t1, launch, shard, cause, extra in profile.records():
        assert t1 >= t0
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
    causes = [c for p, _a, _b, _l, _s, c, _e in profile.records()
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
    logs = {}
    for fn in os.listdir(tmp_path / "log"):
        if fn.endswith(".log"):
            with open(tmp_path / "log" / fn) as f:
                logs[fn.split("_", 2)[2][:-4]] = json.load(f)
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


def test_source_fused_with_a_map_reports_the_maps_time(tmp_path):
    def slow_double(batch):
        time.sleep(0.004)
        batch["value"] *= 2

    ids = np.arange(64)
    batches = []
    for i in range(10):
        b = np.zeros(64, dtype=SCHEMA.dtype())
        b["key"], b["id"], b["ts"] = ids % 2, ids + 64 * i, ids + 64 * i
        batches.append(b)
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
               and ln["t1_ns"] >= ln["t0_ns"] for ln in lines)
    assert len({ln["launch"] for ln in lines}) == 30


# ----------------------------------------------------------- family names

KP, CAP, RB, C, B = 8, 256, 32, 8, 16
I8, I32 = np.dtype(np.int8).str, np.dtype(np.int32).str


def _S(shape, dt):
    return jax.ShapeDtypeStruct(shape, dt)


def _family(name):
    k = _S((KP,), jnp.int32)
    b = _S((B,), jnp.int32)
    ring, blk = _S((KP, CAP), jnp.int32), _S((KP, RB), jnp.int8)
    if name == "wf_step_regular":
        fn = resident._make_regular_step(
            ("reg", "sum", CAP, RB, KP, C, I8, I32, 4))
        return fn, (ring, blk, k, k, k, k)
    if name == "wf_step_append_eval":
        fn = resident._make_step((("max",), CAP, RB, B, KP, I8, I32, 16))
        return fn, (ring, blk, k, b, b, b)
    if name == "wf_step_multi":
        key = (("a", "b"), (("sum", "a"), ("max", "b")), None, CAP, RB, B,
               KP, (I8, I8), (I32, I32), 16)
        fn = resident._make_multi_step(key, None)
        return fn, ((ring, ring), (blk, blk), k, b, b, b, b, b)
    if name == "wf_step_argext":
        key = ("argext", ("a", "b"), (("argmax", "a"), ("sum", "b")), CAP,
               RB, B, KP, (I8, I8), (I32, I32), 0, 256)
        fn = resident._make_argext_step(key)
        return fn, ((ring, ring), (blk, blk), k, k, b, b, b)
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:4]), ("kf",))
    d = _S((4, B), jnp.int32)
    if name == "wf_step_regular_mesh":
        fn = resident._make_mesh_regular_step(
            ("mesh-reg", "sum", CAP, RB, KP, C, I8, I32, 4, mesh, "kf"))
        return fn, (ring, blk, k, k, k, k)
    if name == "wf_step_append_eval_mesh":
        fn = resident._make_mesh_step(
            ("mesh", ("max",), CAP, RB, B, KP, I8, I32, 16, mesh, "kf"))
        return fn, (ring, blk, k, d, d, d)
    assert name == "wf_step_multi_mesh"
    key = ("mesh-multi", ("a", "b"), (("sum", "a"), ("max", "b")), None,
           CAP, RB, B, KP, (I8, I8), (I32, I32), 16, mesh, "kf")
    fn = resident._make_mesh_multi_step(key, None)
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
    key = ("reg", "sum", 1024, 16, 8, 8, I8, I32, 4)
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
