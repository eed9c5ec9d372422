"""Nesting differential tests: WF(PF), WF(WMR), KF(PF), KF(WMR) must match
Win_Seq on the same stream — the compositions exercised by the reference's
test_{wf,kf}+{pf,wm}_* programs and test_all harness."""

import pytest

from windflow_tpu.core.windows import WinType
from windflow_tpu.ops.functions import Reducer
from windflow_tpu.patterns.nesting import KeyFarmOf, WinFarmOf
from windflow_tpu.patterns.pane_farm import PaneFarm
from windflow_tpu.patterns.win_mapreduce import WinMapReduce
from windflow_tpu.patterns.win_seq import WinSeq

from test_farms import (cb_stream_batches, dense_fire_counts, run_windowed,
                        tb_stream_batches)
from test_pane_wmr import iv


def assert_inner_wlqs_fired_complete(df, got, win, slide, n_cores):
    """Every inner Pane_Farm's WLQ cores were told their input is dense,
    and all but the windows the stream's end cut short (at most
    ceil(win/slide) a key, whichever replica owns them) were fired by their
    last pane."""
    counts = dense_fire_counts(df)
    assert len(counts) == n_cores
    total = sum(len(rs) for rs in got.values())
    assert total >= sum(counts) >= total - len(got) * -(-win // slide) > 0


def ref_results(win, slide, wt, batches):
    return iv(run_windowed(WinSeq(Reducer("sum"), win, slide, wt), batches))


@pytest.mark.parametrize("outer", [2, 3])
@pytest.mark.parametrize("plq,wlq", [(1, 1), (2, 2)])
def test_wf_of_pf_cb(outer, plq, wlq):
    # private slide = slide*outer must stay < win (pane_farm sliding check)
    win, slide, keys, n = 16, 4, 3, 140
    inner = PaneFarm(Reducer("sum"), Reducer("sum"), win, slide, WinType.CB,
                     plq_degree=plq, wlq_degree=wlq)
    graph = []
    got = iv(run_windowed(WinFarmOf(inner, pardegree=outer),
                          cb_stream_batches(keys, n), graph))
    assert got == ref_results(win, slide, WinType.CB, cb_stream_batches(keys, n))
    assert_inner_wlqs_fired_complete(graph[0], got, win, slide, outer * wlq)


def test_wf_of_pf_tb():
    win, slide, keys, n = 60, 12, 2, 150
    inner = PaneFarm(Reducer("sum"), Reducer("sum"), win, slide, WinType.TB)
    got = iv(run_windowed(WinFarmOf(inner, pardegree=3),
                          tb_stream_batches(keys, n)))
    assert got == ref_results(win, slide, WinType.TB, tb_stream_batches(keys, n))


@pytest.mark.parametrize("outer", [2, 3])
@pytest.mark.parametrize("map_d,red_d", [(2, 1), (3, 2)])
def test_wf_of_wmr_cb(outer, map_d, red_d):
    win, slide, keys, n = 12, 3, 3, 130
    inner = WinMapReduce(Reducer("sum"), Reducer("sum"), win, slide,
                         WinType.CB, map_degree=map_d, reduce_degree=red_d)
    got = iv(run_windowed(WinFarmOf(inner, pardegree=outer),
                          cb_stream_batches(keys, n)))
    assert got == ref_results(win, slide, WinType.CB, cb_stream_batches(keys, n))


@pytest.mark.parametrize("outer", [2, 4])
@pytest.mark.parametrize("plq,wlq", [(1, 1), (2, 1)])
def test_kf_of_pf_cb(outer, plq, wlq):
    win, slide, keys, n = 12, 4, 5, 120
    inner = PaneFarm(Reducer("sum"), Reducer("sum"), win, slide, WinType.CB,
                     plq_degree=plq, wlq_degree=wlq)
    graph = []
    got = iv(run_windowed(KeyFarmOf(inner, pardegree=outer),
                          cb_stream_batches(keys, n), graph))
    assert got == ref_results(win, slide, WinType.CB, cb_stream_batches(keys, n))
    assert_inner_wlqs_fired_complete(graph[0], got, win, slide, outer * wlq)


@pytest.mark.parametrize("outer", [2, 3])
@pytest.mark.parametrize("map_d", [2, 3])
def test_kf_of_wmr_cb(outer, map_d):
    win, slide, keys, n = 10, 5, 4, 120
    inner = WinMapReduce(Reducer("sum"), Reducer("sum"), win, slide,
                         WinType.CB, map_degree=map_d)
    got = iv(run_windowed(KeyFarmOf(inner, pardegree=outer),
                          cb_stream_batches(keys, n)))
    assert got == ref_results(win, slide, WinType.CB, cb_stream_batches(keys, n))


def test_kf_of_wmr_tb():
    win, slide, keys, n = 45, 15, 3, 140
    inner = WinMapReduce(Reducer("sum"), Reducer("sum"), win, slide,
                         WinType.TB, map_degree=2, reduce_degree=2)
    got = iv(run_windowed(KeyFarmOf(inner, pardegree=2),
                          tb_stream_batches(keys, n)))
    assert got == ref_results(win, slide, WinType.TB, tb_stream_batches(keys, n))


def test_nested_incremental_stages():
    win, slide, keys, n = 16, 4, 3, 120
    inner = PaneFarm(Reducer("sum"), Reducer("sum"), win, slide, WinType.CB,
                     plq_degree=2, wlq_degree=1, plq_incremental=True,
                     wlq_incremental=True)
    got = iv(run_windowed(WinFarmOf(inner, pardegree=2),
                          cb_stream_batches(keys, n)))
    assert got == ref_results(win, slide, WinType.CB, cb_stream_batches(keys, n))
