"""LEVEL1/LEVEL2 graph optimisation (runtime/farm.py:fuse_two_stage — the
reference's optimize_PaneFarm / optimize_WinMapReduce, pane_farm.hpp:426-466)
and the multi-emitter Win_Farm path (win_farm.hpp:147-166): differential
against Win_Seq plus node-count assertions showing the graph shrinks."""

import numpy as np
import pytest

from windflow_tpu.api.builders import (LEVEL1, LEVEL2, PaneFarm_Builder,
                                       WinMapReduce_Builder)
from windflow_tpu.core.windows import WinType
from windflow_tpu.ops.functions import Reducer
from windflow_tpu.patterns.basic import Sink, Source
from windflow_tpu.patterns.pane_farm import PaneFarm
from windflow_tpu.patterns.win_farm import WinFarm
from windflow_tpu.patterns.win_mapreduce import WinMapReduce
from windflow_tpu.patterns.win_seq import WinSeq
from windflow_tpu.runtime.engine import Dataflow
from windflow_tpu.runtime.farm import add_farm, build_pipeline

from test_farms import (SCHEMA, assert_wlq_fired_complete,
                        cb_stream_batches, dense_fire_counts, run_windowed,
                        tb_stream_batches)

KEYS, N = 3, 140
WIN, SLIDE = 12, 4


def stream(wt):
    return (cb_stream_batches(KEYS, N) if wt is WinType.CB
            else tb_stream_batches(KEYS, N))


def totals(per_key):
    return sum(v for rs in per_key.values() for _, _, v in rs)


def graph_node_count(pattern, batches):
    df = Dataflow()
    build_pipeline(df, [Source(batches=iter(batches), schema=SCHEMA),
                        pattern, Sink(lambda r: None)])
    return len(df.nodes)


# ------------------------------------------------------------ differential

@pytest.mark.parametrize("wt", [WinType.CB, WinType.TB], ids=["cb", "tb"])
@pytest.mark.parametrize("level", [LEVEL1, LEVEL2])
@pytest.mark.parametrize("inc", [False, True], ids=["nic", "inc"])
def test_pane_farm_opt_matches_seq(wt, level, inc):
    ref = totals(run_windowed(
        WinSeq(Reducer("sum"), WIN, SLIDE, wt, incremental=inc), stream(wt)))
    for degs in ((1, 1), (3, 1), (1, 3), (2, 3)):
        pf = PaneFarm(Reducer("sum"), Reducer("sum"), WIN, SLIDE, wt,
                      plq_degree=degs[0], wlq_degree=degs[1],
                      plq_incremental=inc, wlq_incremental=inc,
                      opt_level=level)
        graph = []
        got = run_windowed(pf, stream(wt), graph)
        assert totals(got) == ref, f"degs={degs}"
        assert_wlq_fired_complete(graph[0], got, stream(wt), WIN, SLIDE, wt,
                                  degs[1])


@pytest.mark.parametrize("wt", [WinType.CB, WinType.TB], ids=["cb", "tb"])
@pytest.mark.parametrize("level", [LEVEL1, LEVEL2])
def test_wmr_opt_matches_seq(wt, level):
    ref = totals(run_windowed(
        WinSeq(Reducer("sum"), WIN, SLIDE, wt), stream(wt)))
    for map_deg, red_deg in ((2, 1), (3, 2)):
        wmr = WinMapReduce(Reducer("sum"), Reducer("sum"), WIN, SLIDE, wt,
                           map_degree=map_deg, reduce_degree=red_deg,
                           opt_level=level)
        got = run_windowed(wmr, stream(wt))
        assert totals(got) == ref, f"degs={(map_deg, red_deg)}"


def test_opt_results_in_order():
    """LEVEL2's OrderingCore merge must preserve per-key result order."""
    pf = PaneFarm(Reducer("sum"), Reducer("sum"), WIN, SLIDE, WinType.CB,
                  plq_degree=3, wlq_degree=2, opt_level=LEVEL2)
    got = run_windowed(pf, stream(WinType.CB))
    for key, rs in got.items():
        ids = [i for i, _, _ in rs]
        assert ids == sorted(ids), f"key {key} out of order"


# ------------------------------------------------------------- node counts

def test_opt_levels_shrink_graph():
    def pf(level):
        return PaneFarm(Reducer("sum"), Reducer("sum"), WIN, SLIDE,
                        WinType.CB, plq_degree=3, wlq_degree=2,
                        opt_level=level)
    n0 = graph_node_count(pf(0), stream(WinType.CB))
    n1 = graph_node_count(pf(LEVEL1), stream(WinType.CB))
    n2 = graph_node_count(pf(LEVEL2), stream(WinType.CB))
    # LEVEL1 fuses plq-collector + wlq-emitter (2 threads -> 1);
    # LEVEL2 removes the boundary entirely (emitter clones ride the plq
    # worker threads)
    assert n1 == n0 - 1
    assert n2 <= n0 - 2


def test_opt_level1_seq_seq_single_thread():
    def pf(level):
        return PaneFarm(Reducer("sum"), Reducer("sum"), WIN, SLIDE,
                        WinType.CB, plq_degree=1, wlq_degree=1,
                        opt_level=level)
    n0 = graph_node_count(pf(0), stream(WinType.CB))
    n1 = graph_node_count(pf(LEVEL1), stream(WinType.CB))
    assert n1 == n0 - 1  # the two sequential cores share one thread


def test_builder_withopt_drives_fusion():
    pf = (PaneFarm_Builder(Reducer("sum"), Reducer("sum"))
          .withCBWindow(WIN, SLIDE).withParallelism(2, 2)
          .withOpt(LEVEL2).build())
    assert pf.opt_level == LEVEL2
    wmr = (WinMapReduce_Builder(Reducer("sum"), Reducer("sum"))
           .withCBWindow(WIN, SLIDE).withParallelism(2, 1)
           .withOpt(LEVEL1).build())
    assert wmr.opt_level == LEVEL1
    ref = totals(run_windowed(
        WinSeq(Reducer("sum"), WIN, SLIDE, WinType.CB), stream(WinType.CB)))
    assert totals(run_windowed(pf, stream(WinType.CB))) == ref
    assert totals(run_windowed(wmr, stream(WinType.CB))) == ref


# ------------------------------------------------------- multi-emitter WF

def split_stream(batches, n):
    """Partition a batch stream row-round-robin into n in-order substreams
    (the reference's multi-emitter mode feeds one emitter per upstream
    pipeline, win_farm.hpp:147-166)."""
    outs = [[] for _ in range(n)]
    for b in batches:
        for i in range(n):
            part = b[i::n]
            if len(part):
                outs[i].append(part)
    return outs


@pytest.mark.parametrize("wt", [WinType.CB, WinType.TB], ids=["cb", "tb"])
@pytest.mark.parametrize("pardegree", [2, 3])
def test_multi_emitter_win_farm_matches_seq(wt, pardegree):
    ref = run_windowed(WinSeq(Reducer("sum"), WIN, SLIDE, wt), stream(wt))
    parts = split_stream(stream(wt), 2)

    per_key = {}

    def snk(row):
        if row is not None:
            per_key.setdefault(int(row["key"]), []).append(
                (int(row["id"]), int(row["ts"]), int(row["value"])))

    df = Dataflow()
    sources = []
    for i in range(2):
        s = Source(batches=iter(parts[i]), schema=SCHEMA,
                   name=f"src{i}")._make_replica(0)
        df.add(s)
        sources.append(s)
    wf = WinFarm(Reducer("sum"), WIN, SLIDE, wt, pardegree=pardegree,
                 n_emitters=2)
    tails = add_farm(df, wf, sources)
    snk_node = Sink(snk)._make_replica(0)
    df.add(snk_node)
    for t in tails:
        df.connect(t, snk_node)
    df.run_and_wait_end()

    assert per_key.keys() == ref.keys()
    for k in ref:
        assert per_key[k] == ref[k], f"key {k} mismatch"


def test_multi_emitter_wrong_upstream_count_raises():
    df = Dataflow()
    s = Source(batches=iter(stream(WinType.CB)),
               schema=SCHEMA)._make_replica(0)
    df.add(s)
    wf = WinFarm(Reducer("sum"), WIN, SLIDE, WinType.CB, pardegree=2,
                 n_emitters=2)
    with pytest.raises(ValueError, match="n_emitters"):
        add_farm(df, wf, [s])


def test_opt_level_survives_nesting_clone():
    """clone_with must propagate opt_level so nested replicas keep the
    requested fusion (and stay differentially correct)."""
    from windflow_tpu.patterns.nesting import KeyFarmOf, WinFarmOf
    ref = totals(run_windowed(
        WinSeq(Reducer("sum"), WIN, SLIDE, WinType.CB), stream(WinType.CB)))
    for level in (LEVEL1, LEVEL2):
        pf = PaneFarm(Reducer("sum"), Reducer("sum"), WIN, SLIDE,
                      WinType.CB, plq_degree=2, wlq_degree=2,
                      opt_level=level)
        clone = pf.clone_with("n", slide_len=SLIDE * 2)
        assert clone.opt_level == level
        for nested in (KeyFarmOf(PaneFarm(
                Reducer("sum"), Reducer("sum"), WIN, SLIDE, WinType.CB,
                plq_degree=2, wlq_degree=2, opt_level=level), pardegree=2),):
            assert totals(run_windowed(nested, stream(WinType.CB))) == ref


# ------------------------------------------- TPU two-stage patterns (r3)

@pytest.mark.filterwarnings("ignore:resident device path accumulates")
@pytest.mark.parametrize("wt", [WinType.CB, WinType.TB], ids=["cb", "tb"])
@pytest.mark.parametrize("level", [LEVEL1, LEVEL2])
def test_pane_farm_tpu_opt_matches_seq(wt, level):
    """LEVEL1/LEVEL2 fusion over device-core PaneFarm
    stages (optimize_PaneFarmGPU, pane_farm_gpu.hpp:488-529) — the LEVEL2
    path mutates stage2.n_emitters and fronts workers with OrderingCores,
    which must compose with device-batched workers."""
    from windflow_tpu.patterns.win_seq_tpu import PaneFarmTPU
    ref = totals(run_windowed(
        WinSeq(Reducer("sum"), WIN, SLIDE, wt), stream(wt)))
    for degs in ((1, 1), (3, 1), (2, 3)):
        pf = PaneFarmTPU(Reducer("sum"), Reducer("sum"), WIN, SLIDE, wt,
                         plq_degree=degs[0], wlq_degree=degs[1],
                         batch_len=16, flush_rows=128, opt_level=level)
        graph = []
        got = run_windowed(pf, stream(wt), graph)
        assert totals(got) == ref, f"degs={degs}"
        # a device WLQ of a built-in sum is the native core, which takes
        # the property and keeps the reference's trigger: it counts nothing
        assert dense_fire_counts(graph[0]) == []


@pytest.mark.filterwarnings("ignore:resident device path accumulates")
@pytest.mark.parametrize("level", [0, LEVEL1, LEVEL2])
@pytest.mark.parametrize("wlq", [1, 3])
@pytest.mark.parametrize("plq_dev", [False, True], ids=["plq-host", "plq-dev"])
def test_a_pane_farms_window_stage_fires_a_window_with_its_last_pane(
        plq_dev, wlq, level):
    """Whatever sits between the two stages — an ordered collector and an
    emitter (level 0), the two in one thread (1), ordering merges in front
    of the WLQ workers (2) — the WLQ cores are told their input is dense and
    fire every complete window on its last pane id."""
    from windflow_tpu.patterns.win_seq_tpu import PaneFarmTPU
    wt = WinType.CB
    ref = run_windowed(WinSeq(Reducer("sum"), WIN, SLIDE, wt), stream(wt))
    if plq_dev:
        pf = PaneFarmTPU(Reducer("sum"), Reducer("sum"), WIN, SLIDE, wt,
                         plq_degree=2, wlq_degree=wlq, wlq_on_device=False,
                         batch_len=16, flush_rows=128, opt_level=level)
    else:
        pf = PaneFarm(Reducer("sum"), Reducer("sum"), WIN, SLIDE, wt,
                      plq_degree=2, wlq_degree=wlq, opt_level=level)
    graph = []
    got = run_windowed(pf, stream(wt), graph)
    assert {k: [(i, v) for i, _t, v in rs] for k, rs in got.items()} \
        == {k: [(i, v) for i, _t, v in rs] for k, rs in ref.items()}
    assert_wlq_fired_complete(graph[0], got, stream(wt), WIN, SLIDE, wt, wlq)


@pytest.mark.filterwarnings("ignore:resident device path accumulates")
@pytest.mark.parametrize("wt", [WinType.CB, WinType.TB], ids=["cb", "tb"])
@pytest.mark.parametrize("level", [LEVEL1, LEVEL2])
@pytest.mark.parametrize("reduce_dev", [False, True],
                         ids=["red-host", "red-dev"])
def test_wmr_tpu_opt_matches_seq(wt, level, reduce_dev):
    """LEVEL1/LEVEL2 over WinMapReduceTPU with the MAP stage (and
    optionally REDUCE) device-batched (optimize_WinMapReduceGPU,
    win_mapreduce_gpu.hpp:529-558)."""
    from windflow_tpu.patterns.win_seq_tpu import WinMapReduceTPU
    ref = totals(run_windowed(
        WinSeq(Reducer("sum"), WIN, SLIDE, wt), stream(wt)))
    for map_deg, red_deg in ((2, 1), (3, 2)):
        wmr = WinMapReduceTPU(Reducer("sum"), Reducer("sum"), WIN, SLIDE,
                              wt, map_degree=map_deg, reduce_degree=red_deg,
                              reduce_on_device=reduce_dev, batch_len=16,
                              flush_rows=128, opt_level=level)
        got = run_windowed(wmr, stream(wt))
        assert totals(got) == ref, f"degs={(map_deg, red_deg)}"


@pytest.mark.filterwarnings("ignore:resident device path accumulates")
def test_pane_farm_tpu_opt_shrinks_graph():
    from windflow_tpu.patterns.win_seq_tpu import PaneFarmTPU

    def pf(level):
        return PaneFarmTPU(Reducer("sum"), Reducer("sum"), WIN, SLIDE,
                           WinType.CB, plq_degree=3, wlq_degree=2,
                           batch_len=16, flush_rows=128, opt_level=level)
    n0 = graph_node_count(pf(0), stream(WinType.CB))
    n1 = graph_node_count(pf(LEVEL1), stream(WinType.CB))
    n2 = graph_node_count(pf(LEVEL2), stream(WinType.CB))
    assert n1 == n0 - 1
    assert n2 <= n0 - 2


@pytest.mark.filterwarnings("ignore:resident device path accumulates")
def test_pane_farm_tpu_opt_results_in_order():
    from windflow_tpu.patterns.win_seq_tpu import PaneFarmTPU
    pf = PaneFarmTPU(Reducer("sum"), Reducer("sum"), WIN, SLIDE, WinType.CB,
                     plq_degree=3, wlq_degree=2, batch_len=16,
                     flush_rows=128, opt_level=LEVEL2)
    got = run_windowed(pf, stream(WinType.CB))
    for key, rs in got.items():
        ids = [i for i, _, _ in rs]
        assert ids == sorted(ids), f"key {key} out of order"
