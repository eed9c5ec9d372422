"""Overload-policy tests (runtime/overload.py): shedding disciplines, put
deadlines, and poison-tuple quarantine — run against BOTH inbox
implementations (native C++ ring and Python queue fallback), since the
policies are implemented twice.  The contract under test is
docs/ROBUSTNESS.md: knobs unset => seed-identical behavior; knobs set =>
the graph degrades (sheds / quarantines / fails fast) instead of dying on
the first error or hanging on a stalled stage."""

import importlib.util
import json
import os
import time

import numpy as np
import pytest

from windflow_tpu import (Map_Builder, MultiPipe, Sink_Builder,
                          Source_Builder)
from windflow_tpu.core.tuples import Schema, batch_from_columns
from windflow_tpu.patterns.basic import Map, Sink, Source
from windflow_tpu.runtime.engine import Dataflow
from windflow_tpu.runtime.farm import build_pipeline
from windflow_tpu.runtime.overload import (OverloadError, OverloadPolicy,
                                           SHED_POLICIES)

SCHEMA = Schema(value=np.int64)


@pytest.fixture(params=["native", "python"])
def inbox_kind(request, monkeypatch):
    if request.param == "python":
        monkeypatch.setenv("WF_NO_NATIVE", "1")
    else:
        from windflow_tpu import native
        if not native.available():
            pytest.skip("native library unavailable")
        monkeypatch.delenv("WF_NO_NATIVE", raising=False)
    return request.param


def make_batches(n_batches=200, rows=10, poison_at=()):
    out = []
    for i in range(n_batches):
        vals = np.full(rows, i, dtype=np.int64)
        if i in poison_at:
            vals[0] = -1
        out.append(batch_from_columns(
            SCHEMA, key=np.zeros(rows), id=np.arange(rows),
            ts=np.arange(rows), value=vals))
    return out


def run_source_sink(policy, n_batches=200, sink_delay=0.005, capacity=4):
    """Fast source -> slow sink, two nodes, single edge: the conservation
    equation delivered + shed == emitted holds exactly."""
    delivered = [0]
    total = [0]

    def consume(rows):
        if rows is not None and len(rows):
            delivered[0] += 1
            total[0] += int(rows["value"].sum())
            if sink_delay:
                time.sleep(sink_delay)

    df = Dataflow(capacity=capacity, overload=policy)
    build_pipeline(df, [Source(batches=make_batches(n_batches),
                               schema=SCHEMA),
                        Sink(consume, vectorized=True)])
    t0 = time.monotonic()
    df.run_and_wait_end()
    return delivered[0], total[0], df, time.monotonic() - t0


# ------------------------------------------------------------- shedding

@pytest.mark.parametrize("shed", ["shed_oldest", "shed_newest"])
def test_shedding_bounds_slow_sink(inbox_kind, shed):
    """Fast source + slow sink under a shedding policy: the run completes
    quickly (the source never waits on the sink), queue occupancy stays
    bounded by construction, shed counters are nonzero and conserved."""
    n = 200
    delivered, _, df, wall = run_source_sink(OverloadPolicy(shed=shed),
                                             n_batches=n)
    shed_counts = df.shed_counts()
    assert delivered < n
    assert sum(shed_counts.values()) > 0
    # exact conservation on the single sink inbox
    assert delivered + shed_counts["sink.0"] == n
    # a blocking run would take ~n * sink_delay = 1s+; shedding must not
    assert wall < 5.0


def test_block_policy_still_backpressures(inbox_kind):
    """The explicit block policy (and the no-policy default) delivers
    everything: backpressure, no shedding."""
    n = 60
    for policy in (None, OverloadPolicy(shed="block")):
        delivered, total, df, _ = run_source_sink(policy, n_batches=n,
                                                  sink_delay=0.002)
        assert delivered == n
        assert total == sum(10 * i for i in range(n))
        assert df.shed_counts() == {}


def test_shed_newest_keeps_oldest_items(inbox_kind):
    """shed_newest drops the incoming item: what was queued first wins,
    so the delivered set is biased to the stream's prefix."""
    delivered_ids = []

    def consume(rows):
        if rows is not None and len(rows):
            delivered_ids.append(int(rows["value"][0]))
            time.sleep(0.005)

    df = Dataflow(capacity=4, overload=OverloadPolicy(shed="shed_newest"))
    build_pipeline(df, [Source(batches=make_batches(100), schema=SCHEMA),
                        Sink(consume, vectorized=True)])
    df.run_and_wait_end()
    assert delivered_ids == sorted(delivered_ids)   # arrival order kept
    assert delivered_ids[0] == 0                    # the head survived


def test_put_deadline_fails_fast_not_hang(inbox_kind):
    """A stage stalled past the put deadline surfaces as OverloadError
    from wait() within bounded wall-clock — never an indefinite hang."""

    def stall(rows):
        if rows is not None:
            time.sleep(0.4)

    df = Dataflow(capacity=2,
                  overload=OverloadPolicy(put_deadline=0.2))
    build_pipeline(df, [Source(batches=make_batches(50), schema=SCHEMA),
                        Sink(stall, vectorized=True)])
    t0 = time.monotonic()
    with pytest.raises(OverloadError, match="deadline"):
        df.run_and_wait_end()
    assert time.monotonic() - t0 < 10


def test_policy_validation():
    with pytest.raises(ValueError, match="must be one of"):
        OverloadPolicy(shed="drop_everything")
    with pytest.raises(ValueError, match="never blocks"):
        OverloadPolicy(shed="shed_oldest", put_deadline=1.0)
    with pytest.raises(ValueError, match="error_budget"):
        OverloadPolicy(error_budget=-1)
    assert [p for p in SHED_POLICIES] == ["block", "shed_oldest",
                                          "shed_newest"]
    # an unbounded queue never fills: shed/deadline knobs would be
    # silently inert, so the combination is rejected loudly
    with pytest.raises(ValueError, match="bounded"):
        Dataflow(capacity=0, overload=OverloadPolicy(shed="shed_oldest"))
    # a pure error-budget policy has no put-side knob: fine unbounded
    Dataflow(capacity=0, overload=OverloadPolicy(error_budget=3))


def test_shedding_confined_to_shed_safe_inboxes(inbox_kind):
    """Internal window-farm edges (multicast copies, dense-id result
    streams) must never shed — only the farm-head emitter and the sink
    may — and a windowed run where nothing sheds is byte-identical to
    the no-policy run (no silent window corruption)."""
    from windflow_tpu.core.windows import WinType
    from windflow_tpu.ops.functions import Reducer
    from windflow_tpu.patterns.win_farm import WinFarm

    def run(policy):
        got = []
        # capacity > batch count: no inbox can ever fill, so a correct
        # implementation sheds nothing anywhere
        df = Dataflow(capacity=16, overload=policy)
        build_pipeline(df, [
            Source(batches=make_batches(8, rows=12), schema=SCHEMA),
            WinFarm(Reducer("sum"), 16, 8, WinType.CB, pardegree=2),
            Sink(lambda r: got.append(r) if r is not None else None,
                 vectorized=True)])
        df.run_and_wait_end()
        rows = sorted((int(r["key"]), int(r["id"]), int(r["value"]))
                      for g in got for r in g)
        return rows, df

    base, _ = run(None)
    shedded, df = run(OverloadPolicy(shed="shed_oldest"))
    # no queue ever filled: nothing sheds, results identical to no-policy
    assert df.shed_counts() == {}
    assert shedded == base
    # and the internal edges genuinely run policy-free inboxes
    for node in df.nodes:
        inbox = df._inboxes[id(node)]
        if not getattr(node, "shed_safe", False):
            assert inbox._policy is None, node.name


def test_put_deadline_not_consumed_by_error_budget(inbox_kind):
    """An OverloadError raised by a downstream put inside svc's emit is
    backpressure failure, NOT a poison tuple: it must fail fast without
    burning the error budget or landing in the dead-letter queue."""

    def stall(rows):
        if rows is not None:
            time.sleep(0.4)

    df = Dataflow(capacity=2,
                  overload=OverloadPolicy(put_deadline=0.2,
                                          error_budget=50))
    build_pipeline(df, [Source(batches=make_batches(50), schema=SCHEMA),
                        Map(lambda b: b, name="fwd", vectorized=True),
                        Sink(stall, vectorized=True)])
    with pytest.raises(OverloadError):
        df.run_and_wait_end()
    assert df.dead_letters == []


def test_shed_newest_observes_graph_failure(inbox_kind):
    """A failed graph must stop a shed_newest producer too: shedding
    never blocks, so the full-queue path is where cancellation is
    observed (an unbounded source would otherwise generate forever)."""

    def boom(rows):
        if rows is not None:
            raise RuntimeError("sink boom")

    df = Dataflow(capacity=2, overload=OverloadPolicy(shed="shed_newest"))
    build_pipeline(df, [Source(batches=make_batches(5000, rows=4),
                               schema=SCHEMA),
                        Sink(boom, vectorized=True)])
    t0 = time.monotonic()
    with pytest.raises(RuntimeError, match="sink boom"):
        df.run_and_wait_end()
    assert time.monotonic() - t0 < 30


# ----------------------------------------------------------- quarantine

def poison_graph(budget_policy=None, node_budget=None, poison_at=(3, 7),
                 n=20, trace_dir=None):
    got = [0]

    def check(b):
        if (b["value"] < 0).any():
            raise ValueError("poison batch")

    def consume(rows):
        if rows is not None and len(rows):
            got[0] += 1

    mp = Map(check, name="check", vectorized=True)
    if node_budget is not None:
        mp.error_budget = node_budget
    df = Dataflow("poison", capacity=4, overload=budget_policy,
                  trace_dir=trace_dir)
    build_pipeline(df, [
        Source(batches=make_batches(n, poison_at=poison_at),
               schema=SCHEMA),
        mp,
        Sink(consume, vectorized=True)])
    return df, got


def test_poison_within_budget_quarantines(inbox_kind):
    """Poison batches within the error budget land in the dead-letter
    queue; the graph runs to completion and the rest of the stream is
    processed normally."""
    df, got = poison_graph(OverloadPolicy(error_budget=3),
                           poison_at=(3, 7), n=20)
    df.run_and_wait_end()
    assert got[0] == 18
    assert len(df.dead_letters) == 2
    dl = df.dead_letters[0]
    assert dl.node == "check.0"
    assert isinstance(dl.error, ValueError)
    assert int(dl.batch["value"][0]) == -1      # the offending batch
    assert "DeadLetter" in repr(dl)


def test_poison_over_budget_fails_fast(inbox_kind):
    """Budget exhausted => the NEXT poison error propagates exactly like
    the default engine (fail-fast preserved), after quarantining up to
    the budget."""
    df, _ = poison_graph(OverloadPolicy(error_budget=2),
                         poison_at=(2, 5, 8, 11), n=20)
    with pytest.raises(ValueError, match="poison"):
        df.run_and_wait_end()
    assert len(df.dead_letters) == 2


def test_poison_default_fails_on_first_error(inbox_kind):
    """No budget set: first poison batch tears the graph down (seed
    behavior) and nothing is quarantined."""
    df, _ = poison_graph(None, poison_at=(4,), n=20)
    with pytest.raises(ValueError, match="poison"):
        df.run_and_wait_end()
    assert df.dead_letters == []


def test_node_budget_overrides_policy():
    """A node-level budget (builders' withErrorBudget path) wins over the
    dataflow-wide default."""
    df, got = poison_graph(OverloadPolicy(error_budget=0), node_budget=5,
                           poison_at=(1, 2, 3), n=12)
    df.run_and_wait_end()
    assert got[0] == 9
    assert len(df.dead_letters) == 3


def test_quarantine_counter_in_tracing(tmp_path):
    d = str(tmp_path / "log")
    df, _ = poison_graph(OverloadPolicy(error_budget=2), poison_at=(3,),
                         n=10, trace_dir=d)
    df.run_and_wait_end()
    # (node logs only: a launches.jsonl lies beside them whenever an
    # earlier test's ship thread closed a span after its profile reset)
    logs = [json.load(open(os.path.join(d, f))) for f in os.listdir(d)
            if f.endswith(".log")]
    check = next(v for v in logs if v["node"].endswith("check.0"))
    assert check["quarantined"] == 1


def test_shed_counter_in_tracing(tmp_path, inbox_kind):
    d = str(tmp_path / "log")
    delivered = [0]

    def consume(rows):
        if rows is not None and len(rows):
            delivered[0] += 1
            time.sleep(0.005)

    df = Dataflow("tr", capacity=4,
                  overload=OverloadPolicy(shed="shed_oldest"),
                  trace_dir=d)
    build_pipeline(df, [Source(batches=make_batches(100), schema=SCHEMA),
                        Sink(consume, vectorized=True)])
    df.run_and_wait_end()
    logs = [json.load(open(os.path.join(d, f))) for f in os.listdir(d)
            if f.endswith(".log")]
    sink = next(v for v in logs if v["node"].endswith("sink.0"))
    assert sink["shed"] == 100 - delivered[0] > 0


# -------------------------------------------------- builder / MultiPipe

def test_with_error_budget_through_multipipe():
    """Fluent path end to end: withErrorBudget on a builder, OverloadPolicy
    on the MultiPipe, dead letters inspectable on the pipe after wait()."""
    got = [0]

    def check(b):
        if (b["value"] < 0).any():
            raise ValueError("poison batch")

    def consume(rows):
        if rows is not None and len(rows):
            got[0] += 1

    pipe = (MultiPipe("robust", overload=OverloadPolicy())
            .add_source(Source_Builder()
                        .withBatches(make_batches(16, poison_at=(5,)))
                        .withSchema(SCHEMA).build())
            .add(Map_Builder(check).vectorized().withErrorBudget(2)
                 .withName("check").build())
            .add_sink(Sink_Builder(consume).vectorized().build()))
    pipe.run_and_wait_end()
    assert got[0] == 15
    assert len(pipe.dead_letters) == 1
    assert pipe.dead_letters[0].node == "check.0"
    assert pipe.shed_counts() == {}


def test_with_error_budget_survives_chaining():
    """chain() fuses operators into one thread; the tightest member
    budget must govern the fused node, not vanish."""
    got = [0]

    def check(b):
        if (b["value"] < 0).any():
            raise ValueError("poison batch")

    def consume(rows):
        if rows is not None and len(rows):
            got[0] += 1

    pipe = (MultiPipe("chained")
            .add_source(Source_Builder()
                        .withBatches(make_batches(12, poison_at=(4,)))
                        .withSchema(SCHEMA).build())
            .add(Map_Builder(check).vectorized().withErrorBudget(2)
                 .withName("check").build())
            .chain(Map_Builder(lambda b: b).vectorized()
                   .withName("fwd").build())
            .add_sink(Sink_Builder(consume).vectorized().build()))
    pipe.run_and_wait_end()
    assert got[0] == 11
    assert len(pipe.dead_letters) == 1      # the chained budget held


def test_with_error_budget_validation():
    with pytest.raises(ValueError, match=">= 0"):
        Map_Builder(lambda b: b).withErrorBudget(-1)


def test_shell_nodes_exempt_from_policy_budget():
    """Framework shells (emitters/collectors/ordering merges) never
    inherit the dataflow-wide budget: an error there is a framework bug,
    and quarantining it would silently corrupt the stream."""
    from windflow_tpu.runtime.emitters import Collector, StandardEmitter
    from windflow_tpu.runtime.ordering import OrderingMode, OrderingNode

    from windflow_tpu.core.windows import WindowSpec, WinType
    from windflow_tpu.core.winseq import WinSeqCore
    from windflow_tpu.ops.functions import Reducer
    from windflow_tpu.patterns.win_seq import WinSeqNode
    from windflow_tpu.runtime.comb import make_comb

    df = Dataflow(overload=OverloadPolicy(error_budget=5))
    win_node = WinSeqNode(WinSeqCore(WindowSpec(4, 2, WinType.CB),
                                     Reducer("sum")))
    for exempt in (StandardEmitter(2), Collector(),
                   OrderingNode(2, OrderingMode.TS),
                   # window cores fold rows into state before raising:
                   # quarantining them would corrupt windows silently
                   win_node):
        assert exempt.quarantine_exempt
        assert df._error_budget_of(exempt) == 0
    # worker nodes DO inherit it
    from windflow_tpu.patterns.basic import Map
    worker = Map(lambda b: b, vectorized=True)._make_replica(0)
    assert df._error_budget_of(worker) == 5
    # a Comb containing any exempt stage inherits fail-fast; a Comb of
    # pure user operators does not
    w2 = Map(lambda b: b, vectorized=True)._make_replica(0)
    assert make_comb([w2, StandardEmitter(2)]).quarantine_exempt
    assert not make_comb(
        [Map(lambda b: b, vectorized=True)._make_replica(0),
         Map(lambda b: b, vectorized=True)._make_replica(0)]
    ).quarantine_exempt


def test_union_rejects_conflicting_policies():
    from windflow_tpu import union_multipipes

    def pipe(policy):
        return (MultiPipe("b", overload=policy)
                .add_source(Source_Builder().withBatches(make_batches(2))
                            .withSchema(SCHEMA).build()))

    with pytest.raises(ValueError, match="conflicting overload"):
        union_multipipes(pipe(OverloadPolicy(shed="shed_oldest")),
                         pipe(OverloadPolicy(put_deadline=2.0)))
    # identical / partially-unset policies merge fine
    merged = union_multipipes(pipe(OverloadPolicy(error_budget=1)),
                              pipe(None))
    assert merged.overload.error_budget == 1


# ------------------------------------- robustness counters, end to end

def test_robustness_counters_end_to_end(inbox_kind, tmp_path):
    """ISSUE 4 satellite: one graph that BOTH sheds (overloaded sink)
    and quarantines (poison within budget) must surface the counters in
    all three observability views — NodeStats.snapshot() (the end-of-run
    .log), the live sampler's metrics.jsonl, and events.jsonl — with
    every line schema-valid (tests/obs_schema.py)."""
    from obs_schema import validate_event, validate_file, validate_sample
    d = str(tmp_path / "e2e")
    delivered = [0]

    def check(b):
        if (b["value"] < 0).any():
            raise ValueError("poison batch")

    def consume(rows):
        if rows is not None and len(rows):
            delivered[0] += 1
            time.sleep(0.004)

    # a paced source keeps the map's inbox drained (the instant map
    # never sheds), so every poison batch deterministically reaches
    # check and quarantines; the slow sink's inbox is the one that
    # overloads and sheds.  Budget exceeds the poison count so the
    # graph always completes.
    batches = make_batches(80, poison_at=tuple(range(0, 80, 10)))

    def gen(shipper):
        for b in batches:
            shipper.push_batch(b.copy())
            time.sleep(0.001)

    df = Dataflow("e2e", capacity=4, trace_dir=d, sample_period=0.005,
                  overload=OverloadPolicy(shed="shed_oldest",
                                          error_budget=80))
    build_pipeline(df, [
        Source(gen, SCHEMA),
        Map(check, name="check", vectorized=True),
        Sink(consume, vectorized=True)])
    df.run_and_wait_end()
    shed_total = sum(df.shed_counts().values())
    assert shed_total > 0 and len(df.dead_letters) >= 1

    # view 1: NodeStats.snapshot() as written to the per-node .log
    logs = {f: json.load(open(os.path.join(d, f)))
            for f in os.listdir(d) if f.endswith(".log")}
    sink_log = next(v for v in logs.values()
                    if v["node"].endswith("sink.0"))
    assert sink_log["shed"] == df.shed_counts()["sink.0"]
    check_log = next(v for v in logs.values()
                     if v["node"].endswith("check.0"))
    assert check_log["quarantined"] == len(df.dead_letters)

    # view 2: the live sampler's metrics.jsonl (schema-valid, and the
    # final sample agrees with the end-of-run accounting)
    mpath = os.path.join(d, "metrics.jsonl")
    assert validate_file(mpath, validate_sample) >= 2
    last = json.loads(open(mpath).read().splitlines()[-1])
    by_node = {n["node"]: n for n in last["nodes"]}
    assert sum(n["shed"] for n in by_node.values()) == shed_total
    assert by_node["check.0"]["quarantined"] == len(df.dead_letters)
    assert last["dead_letters"] == len(df.dead_letters)

    # view 3: events.jsonl carries shed + quarantine events
    epath = os.path.join(d, "events.jsonl")
    assert validate_file(epath, validate_event) > 0
    events = [json.loads(line) for line in open(epath)]
    kinds = {e["event"] for e in events}
    assert {"shed", "quarantine"} <= kinds
    q = next(e for e in events if e["event"] == "quarantine")
    assert q["node"] == "check.0" and q["error"] == "ValueError"
    shed_ev_total = sum(e["n"] for e in events if e["event"] == "shed")
    assert shed_ev_total == shed_total


# ------------------------------------------------------------- slow soak

def _soak_module():
    spec = importlib.util.spec_from_file_location(
        "soak_overload",
        os.path.join(os.path.dirname(os.path.dirname(__file__)),
                     "scripts", "soak_overload.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.slow
def test_overload_soak_small():
    """A small slice of scripts/soak_overload.py (the standalone repro
    harness): randomized policies / capacities / poison patterns, all
    invariants conserved."""
    stats = _soak_module().run_soak(n=60, seed=123)
    assert stats["cases"] == 60
    assert stats["shed_cases"] > 0 and stats["poison_cases"] > 0


@pytest.mark.slow
def test_overload_soak_with_metrics(tmp_path):
    """The soak with the observability layer ON (ISSUE 4 satellite):
    every conservation invariant still holds with the sampler running,
    and the files it leaves behind are schema-valid with live (pre-final)
    samples showing real occupancy."""
    from obs_schema import validate_event, validate_file, validate_sample
    d = str(tmp_path / "soakobs")
    stats = _soak_module().run_soak(n=25, seed=321, trace_dir=d,
                                    sample_period=0.01)
    assert stats["cases"] == 25 and stats["shed_cases"] > 0
    assert validate_file(os.path.join(d, "metrics.jsonl"),
                         validate_sample) >= 25
    assert validate_file(os.path.join(d, "events.jsonl"),
                         validate_event) > 0
    samples = [json.loads(line)
               for line in open(os.path.join(d, "metrics.jsonl"))]
    assert max(n["depth"] for s in samples for n in s["nodes"]) > 0
    assert max(n["shed"] for s in samples for n in s["nodes"]) > 0
