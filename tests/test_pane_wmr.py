"""Differential tests for Pane_Farm and Win_MapReduce vs Win_Seq — the
equivalent of src/sum_test_cpu test_{pf,wm}_{cb,tb}_{nic,inc}. Results are
compared on (id, value) per key: the reference's own ts bookkeeping differs
across compositions (the test_all harness compares totals only); values and
dense window ids must match exactly."""

import numpy as np
import pytest

from windflow_tpu.core.windows import WinType
from windflow_tpu.ops.functions import Reducer
from windflow_tpu.patterns.pane_farm import PaneFarm
from windflow_tpu.patterns.win_mapreduce import WinMapReduce
from windflow_tpu.patterns.win_seq import WinSeq

from test_farms import (assert_wlq_fired_complete, cb_stream_batches,
                        dense_fire_counts, run_windowed, tb_stream_batches)


def iv(per_key):
    return {k: [(r[0], r[2]) for r in rs] for k, rs in per_key.items()}


CASES_CB = [(8, 4), (12, 3), (10, 5), (9, 3)]
CASES_TB = [(40, 20), (30, 10)]


@pytest.mark.parametrize("win,slide", CASES_CB)
@pytest.mark.parametrize("plq,wlq", [(1, 1), (2, 1), (1, 2), (3, 2)])
@pytest.mark.parametrize("inc", [False, True])
def test_pane_farm_cb(win, slide, plq, wlq, inc):
    keys, n = 3, 120
    ref = run_windowed(
        WinSeq(Reducer("sum"), win, slide, WinType.CB, incremental=inc),
        cb_stream_batches(keys, n))
    graph = []
    got = run_windowed(
        PaneFarm(Reducer("sum"), Reducer("sum"), win, slide, WinType.CB,
                 plq_degree=plq, wlq_degree=wlq, plq_incremental=inc,
                 wlq_incremental=inc),
        cb_stream_batches(keys, n), graph)
    assert iv(got) == iv(ref)
    assert_wlq_fired_complete(graph[0], got, cb_stream_batches(keys, n),
                              win, slide, WinType.CB, wlq)


@pytest.mark.parametrize("win,slide", CASES_TB)
@pytest.mark.parametrize("plq,wlq", [(1, 1), (2, 2)])
def test_pane_farm_tb(win, slide, plq, wlq):
    keys, n = 2, 150
    ref = run_windowed(WinSeq(Reducer("sum"), win, slide, WinType.TB),
                       tb_stream_batches(keys, n))
    graph = []
    got = run_windowed(
        PaneFarm(Reducer("sum"), Reducer("sum"), win, slide, WinType.TB,
                 plq_degree=plq, wlq_degree=wlq),
        tb_stream_batches(keys, n), graph)
    assert iv(got) == iv(ref)
    assert_wlq_fired_complete(graph[0], got, tb_stream_batches(keys, n),
                              win, slide, WinType.TB, wlq)


@pytest.mark.parametrize("wlq,level", [(1, 0), (2, 0), (1, 1), (2, 2)])
def test_a_window_reaches_the_sink_with_its_last_pane_and_no_later_row(
        wlq, level):
    """Windows of 12 ids every 4: panes of 4, three a window.  The PLQ runs
    over the user's stream and keeps the reference's rule, so window 0's
    last pane, ids [8, 12), is closed by id 12.  The source sends ids 0..12
    and then NOTHING until the sink holds window 0.  Under the reference's
    rule in the WLQ too, window 0 waits for pane 3's result, that is for
    id 16, which this source never sends: it would see the end of the
    stream first."""
    import threading
    from windflow_tpu.api import MultiPipe
    from windflow_tpu.core.tuples import Schema, batch_from_columns
    from windflow_tpu.patterns.basic import Sink, Source
    schema = Schema(value=np.int64)
    at_sink, waited, got = threading.Event(), [], []

    def src(shipper):
        ids = np.arange(13)
        shipper.push_batch(batch_from_columns(
            schema, key=np.zeros(13), id=ids, ts=ids * 7, value=ids))
        waited.append(at_sink.wait(timeout=60))    # a bound, not a pace

    def snk(rows):
        if rows is not None and len(rows):
            got.extend(rows["id"].tolist())
            if 0 in rows["id"]:
                at_sink.set()

    (MultiPipe("pf_live")
     .add_source(Source(src, schema, fresh=True))
     .add(PaneFarm(Reducer("sum"), Reducer("sum"), 12, 4, WinType.CB,
                   plq_degree=1, wlq_degree=wlq, opt_level=level))
     .chain_sink(Sink(snk, vectorized=True))).run_and_wait_end(timeout=120)
    assert waited == [True]
    assert got == [0, 1, 2, 3]      # the flush brought the cut-short ones


def test_pane_farm_rejects_non_sliding():
    with pytest.raises(ValueError, match="sliding"):
        PaneFarm(Reducer("sum"), Reducer("sum"), 5, 5, WinType.CB)


@pytest.mark.parametrize("win,slide", CASES_CB + [(3, 8)])
@pytest.mark.parametrize("map_d,red_d", [(2, 1), (3, 1), (2, 2), (4, 3)])
@pytest.mark.parametrize("inc", [False, True])
def test_win_mapreduce_cb(win, slide, map_d, red_d, inc):
    keys, n = 3, 110
    ref = run_windowed(
        WinSeq(Reducer("sum"), win, slide, WinType.CB, incremental=inc),
        cb_stream_batches(keys, n))
    graph = []
    got = run_windowed(
        WinMapReduce(Reducer("sum"), Reducer("sum"), win, slide, WinType.CB,
                     map_degree=map_d, reduce_degree=red_d,
                     map_incremental=inc, reduce_incremental=inc),
        cb_stream_batches(keys, n), graph)
    assert iv(got) == iv(ref)
    # a REDUCE stage is told nothing of its input: the reference's rule
    assert dense_fire_counts(graph[0]) == []


@pytest.mark.parametrize("win,slide", CASES_TB + [(10, 25)])
@pytest.mark.parametrize("map_d,red_d", [(2, 1), (3, 2)])
def test_win_mapreduce_tb(win, slide, map_d, red_d):
    keys, n = 2, 140
    ref = run_windowed(WinSeq(Reducer("sum"), win, slide, WinType.TB),
                       tb_stream_batches(keys, n))
    got = run_windowed(
        WinMapReduce(Reducer("sum"), Reducer("sum"), win, slide, WinType.TB,
                     map_degree=map_d, reduce_degree=red_d),
        tb_stream_batches(keys, n))
    assert iv(got) == iv(ref)


def test_win_mapreduce_rejects_serial_map():
    with pytest.raises(ValueError, match="parallel MAP"):
        WinMapReduce(Reducer("sum"), Reducer("sum"), 8, 4, map_degree=1)


def test_all_compositions_equal_totals():
    """The reference's test_all_cb differential harness: Win_Seq first, then
    every composition on the SAME stream must give the same total sum
    (test_all_cb.cpp:171-473)."""
    from windflow_tpu.patterns.key_farm import KeyFarm
    from windflow_tpu.patterns.win_farm import WinFarm

    keys, n, win, slide = 4, 150, 12, 4
    stream = lambda: cb_stream_batches(keys, n)

    def total(per_key):
        return sum(v for rs in per_key.values() for _, _, v in rs)

    ref = total(run_windowed(WinSeq(Reducer("sum"), win, slide, WinType.CB),
                             stream()))
    compositions = [
        WinFarm(Reducer("sum"), win, slide, WinType.CB, pardegree=3),
        KeyFarm(Reducer("sum"), win, slide, WinType.CB, pardegree=3),
        PaneFarm(Reducer("sum"), Reducer("sum"), win, slide, WinType.CB,
                 plq_degree=2, wlq_degree=2),
        WinMapReduce(Reducer("sum"), Reducer("sum"), win, slide, WinType.CB,
                     map_degree=3, reduce_degree=2),
    ]
    for comp in compositions:
        assert total(run_windowed(comp, stream())) == ref, comp
