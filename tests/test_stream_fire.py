"""Stream-time firing (``fire_on="stream"``): time-based windows that close on
the stage's time and not the key's, keys retired when they go quiet, progress
rows that cross the stages, and NEXMark Q5's two-stage pipeline against the
plain reference (``oracle.hot_items_windows``)."""

import json
import threading

import numpy as np
import pytest

from oracle import hot_items_windows
from windflow_tpu.api import MultiPipe
from windflow_tpu.core.slots import SlotMap
from windflow_tpu.core.tuples import (MARKER_FIELD, Schema,
                                      batch_from_columns, progress_row)
from windflow_tpu.core.vecinc import VecStreamCore, make_vec_core
from windflow_tpu.core.windows import PatternConfig, Role, WindowSpec, WinType
from windflow_tpu.core.winseq import WinSeqCore
from windflow_tpu.ops.functions import ArgReducer, MultiReducer, Reducer
from windflow_tpu.patterns.basic import Map, Sink, Source
from windflow_tpu.patterns.key_farm import KeyFarm
from windflow_tpu.patterns.win_seq import WinSeq, window_cores
from windflow_tpu.patterns.win_seq_tpu import KeyFarmTPU, WinSeqTPU
from windflow_tpu.runtime.ordering import ProgressMerge

VALUE = Schema(value=np.int64)
SPECS = [(10, 5), (10, 10), (12, 5), (7, 7)]
STATS = MultiReducer(Reducer("count", out_field="count"),
                     Reducer("sum", "value", "total"),
                     Reducer("max", "ts", "last"))


def quiet_stream(seed, n=1500, span=300):
    """An in-order stream whose key space grows and whose keys go quiet."""
    rng = np.random.default_rng(seed)
    ts = np.sort(rng.integers(0, span, n))
    keys = ts // 3 + rng.integers(0, 4, n)
    return batch_from_columns(VALUE, key=keys, id=np.arange(n), ts=ts,
                              value=rng.integers(0, 100, n))


def busy_stream(seed, n=1200, span=200, keys=5):
    """Every key has a row in every time unit."""
    rng = np.random.default_rng(seed)
    ts = np.repeat(np.arange(span), keys)
    key = np.tile(np.arange(keys), span)
    extra = np.sort(rng.integers(0, span, n))
    ts = np.concatenate((ts, extra))
    key = np.concatenate((key, rng.integers(0, keys, n)))
    order = np.argsort(ts, kind="stable")
    return batch_from_columns(VALUE, key=key[order], id=np.arange(len(ts)),
                              ts=ts[order], value=rng.integers(0, 9, len(ts)))


def brute(batch, win, slide):
    """``{(key, window): [count, sum, last ts]}`` by a loop over rows."""
    out = {}
    for r in batch:
        k, t = int(r["key"]), int(r["ts"])
        for w in range(max((t - win) // slide + 1, 0), t // slide + 1):
            c = out.setdefault((k, w), [0, 0, -1])
            c[0] += 1
            c[1] += int(r["value"])
            c[2] = max(c[2], t)
    return out


def run_core(core, batch, seed, flush=True):
    """Feed ``batch`` in chunks of random sizes; the outputs of the
    processing phase and of the end-of-stream flush."""
    rng = np.random.default_rng(seed)
    outs, lo = [], 0
    while lo < len(batch):
        step = int(rng.integers(1, 400))
        outs.append(core.process(batch[lo:lo + step]))
        lo += step
    during = np.concatenate(outs)
    return during, (core.flush() if flush else during[:0])


def as_dict(rows, fields=("count", "total", "last")):
    real = rows[~rows[MARKER_FIELD]]
    out = {(int(r["key"]), int(r["id"])): [int(r[f]) for f in fields]
           for r in real}
    assert len(out) == len(real)            # each (key, window) once
    return out


def sum_core(kind, spec, fire_on):
    if kind == "vec":
        return make_vec_core(spec, Reducer("sum"), fire_on=fire_on)
    core = WinSeqCore(spec, Reducer("sum"), fire_on=fire_on)
    return core.use_incremental() if kind == "inc" else core


# ------------------------------------------------------------------ the cores

@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("win,slide", SPECS)
def test_vec_stream_core_against_a_loop(win, slide, seed):
    batch = quiet_stream(seed)
    core = make_vec_core(WindowSpec(win, slide, WinType.TB), STATS,
                         fire_on="stream")
    assert type(core) is VecStreamCore
    during, flushed = run_core(core, batch, seed)
    assert as_dict(np.concatenate((during, flushed))) == brute(batch, win,
                                                               slide)
    real = during[~during[MARKER_FIELD]]
    assert (real["ts"] == real["id"] * slide + win - 1).all()
    # what left while the stream ran left in time order, progress rows too
    assert (np.diff(during["ts"]) >= 0).all()
    assert core.keys_live == 0 and core.keys_retired > 0


@pytest.mark.parametrize("kind", ["nic", "inc"])
@pytest.mark.parametrize("win,slide", SPECS + [(4, 9)])
def test_winseq_stream_core_against_a_loop(win, slide, kind):
    batch = quiet_stream(7)
    core = sum_core(kind, WindowSpec(win, slide, WinType.TB), "stream")
    during, flushed = run_core(core, batch, 7)
    want = {}
    for r in batch:
        k, t = int(r["key"]), int(r["ts"])
        if slide > win:                             # hopping: gaps
            wids = [t // slide] if t % slide < win else []
        else:
            wids = range(max((t - win) // slide + 1, 0), t // slide + 1)
        for w in wids:
            want[(k, w)] = [want.get((k, w), [0])[0] + int(r["value"])]
    assert as_dict(np.concatenate((during, flushed)), ("value",)) == want
    assert (np.diff(during["ts"]) >= 0).all()
    assert core.keys_retired > 0


@pytest.mark.parametrize("kind", ["vec", "nic", "inc"])
@pytest.mark.parametrize("win,slide", SPECS)
def test_stream_equals_key_where_every_key_stays_busy(win, slide, kind):
    batch = busy_stream(3)
    spec = WindowSpec(win, slide, WinType.TB)
    got = [run_core(sum_core(kind, spec, fire), batch, 11)
           for fire in ("stream", "key")]
    (s_during, s_flush), (k_during, k_flush) = got
    assert as_dict(s_during, ("value",)) == as_dict(k_during, ("value",))
    assert as_dict(s_flush, ("value",)) == as_dict(k_flush, ("value",))


@pytest.mark.parametrize("kind", ["vec", "nic"])
@pytest.mark.parametrize("win,slide", SPECS)
def test_stream_equals_key_on_any_stream_but_for_empty_windows(win, slide,
                                                               kind):
    """Key mode fires a key's skipped windows with the identity and holds a
    quiet key's last windows back until the stream ends; the windows with a
    row are the same."""
    batch = quiet_stream(5)
    spec = WindowSpec(win, slide, WinType.TB)
    stream = np.concatenate(run_core(sum_core(kind, spec, "stream"),
                                     batch, 13))
    keyed = np.concatenate(run_core(
        WinSeqCore(spec, MultiReducer(Reducer("sum"),
                                      Reducer("count", out_field="n"))),
        batch, 13))
    keyed = keyed[keyed["n"] > 0]
    assert as_dict(stream, ("value",)) == as_dict(keyed, ("value",))


@pytest.mark.parametrize("kind", ["vec", "nic", "inc"])
def test_a_stream_that_ends_inside_its_first_window_flushes_the_same(kind):
    batch = quiet_stream(9, n=200, span=9)
    spec = WindowSpec(10, 5, WinType.TB)
    s_during, s_flush = run_core(sum_core(kind, spec, "stream"), batch, 1)
    k_during, k_flush = run_core(sum_core(kind, spec, "key"), batch, 1)
    assert not len(s_during) and not len(k_during)
    assert as_dict(s_flush, ("value",)) == as_dict(k_flush, ("value",))


@pytest.mark.parametrize("kind", ["vec", "nic"])
def test_state_holds_only_the_open_windows_keys(kind):
    """``keys_live`` stays flat while ``keys_retired`` grows."""
    spec = WindowSpec(10, 5, WinType.TB)
    core = sum_core(kind, spec, "stream")
    live, retired = [], []
    for w in range(40):                     # 20 new keys a slide, for ever
        ts = np.repeat(np.arange(w * 5, w * 5 + 5), 4)
        keys = 1000 + w * 20 + np.arange(20)
        core.process(batch_from_columns(VALUE, key=keys, id=np.arange(20),
                                        ts=ts, value=np.ones(20)))
        live.append(core.keys_live)
        retired.append(core.keys_retired)
    assert max(live) <= 60 and core.keys_live_peak <= 60
    assert live[10:] == [live[10]] * 30       # flat
    assert retired[-1] >= 20 * 36 and retired[-1] > retired[10]


@pytest.mark.parametrize("kind", ["vec", "nic", "inc"])
def test_a_key_that_returns_after_it_was_retired_is_a_new_key(kind):
    spec = WindowSpec(10, 5, WinType.TB)
    core = sum_core(kind, spec, "stream")
    rows = batch_from_columns(
        VALUE, key=[7, 8, 8, 7, 8], id=np.arange(5), ts=[1, 2, 31, 33, 52],
        value=[10, 1, 1, 20, 1])
    out = np.concatenate((core.process(rows[:2]), core.process(rows[2:3]),
                          core.process(rows[3:]), core.flush()))
    assert core.keys_retired >= 2
    # key 7: window 0 from its first life; windows 5 and 6 from its second,
    # and none of the windows in between
    assert {w: v for (k, w), (v,) in as_dict(out, ("value",)).items()
            if k == 7} == {0: 10, 5: 20, 6: 20}


def test_a_window_without_a_row_gives_no_result():
    core = make_vec_core(WindowSpec(10, 5, WinType.TB), Reducer("sum"),
                         fire_on="stream")
    rows = batch_from_columns(VALUE, key=[1, 1], id=[0, 1], ts=[2, 48],
                              value=[5, 6])
    out = np.concatenate((core.process(rows), core.flush()))
    assert as_dict(out, ("value",)) == {(1, 0): [5], (1, 8): [6], (1, 9): [6]}
    marks = out[out[MARKER_FIELD]]
    assert marks["id"].tolist() == [7] and marks["ts"].tolist() == [45]


@pytest.mark.parametrize("kind", ["vec", "nic"])
def test_a_marker_row_moves_the_clock_and_folds_nothing(kind):
    core = sum_core(kind, WindowSpec(10, 5, WinType.TB), "stream")
    rows = batch_from_columns(VALUE, key=[1, 2], id=[0, 1], ts=[2, 3],
                              value=[5, 6])
    assert not len(core.process(rows))
    mark = progress_row(rows.dtype, 0, 20)
    out = core.process(mark)
    assert as_dict(out, ("value",)) == {(1, 0): [5], (2, 0): [6]}
    assert out[MARKER_FIELD].tolist() == [False, False, True]
    assert core.keys_live == 0 and not len(core.flush())


@pytest.mark.parametrize("make,why", [
    (lambda: WinSeq(Reducer("sum"), 10, 5, WinType.CB, fire_on="stream"),
     "time-based"),
    (lambda: WinSeq(Reducer("sum"), 10, 5, WinType.TB, fire_on="stream",
                    role=Role.MAP), "plain sequential"),
    (lambda: WinSeq(Reducer("sum"), 10, 5, WinType.TB, fire_on="stream",
                    config=PatternConfig(0, 2, 5, 0, 1, 5)),
     "plain sequential"),
    # a *TPU pattern learns its core when the graph is built, before any
    # thread starts (make_core_for, plan_core's caller)
    # (a sum over time-based windows runs on the native resident core since
    # PR 33, tests/test_late_events.py; what that core cannot keep refuses)
    (lambda: WinSeqTPU(Reducer("sum"), 10, 5, WinType.TB, fire_on="stream",
                       shards=2).make_core(), "one shard on one device"),
    (lambda: KeyFarmTPU(Reducer("sum"), 10, 5, WinType.TB, fire_on="stream",
                        use_resident=False).replicas(), "host window cores"),
    (lambda: WinSeqTPU(Reducer("sum"), 5, 10, WinType.TB,
                       fire_on="stream").make_core(), "hopping windows"),
    (lambda: WinSeqTPU(Reducer("sum"), 10, 5, WinType.TB, fire_on="stream",
                       max_delay_ms=5).make_core(), "wall clock"),
    (lambda: WinSeq(Reducer("sum"), 10, 5, WinType.TB, holdback=3),
     "belongs to fire_on='stream'"),
    (lambda: WinSeq(Reducer("sum"), 10, 5, WinType.TB, fire_on="stream",
                    holdback=-1), "span of time"),
    (lambda: WinSeqTPU(Reducer("count"), 10, 5, WinType.CB,
                       fire_on="stream").make_core(), "time-based"),
    (lambda: WinSeq(Reducer("sum"), 10, 5, WinType.TB, fire_on="watermark"),
     "fire_on is"),
])
def test_where_a_core_cannot_honour_it_the_pattern_refuses(make, why):
    with pytest.raises(ValueError, match=why):
        make()


def test_a_tpu_pattern_routed_to_the_host_honours_it():
    cores = [KeyFarmTPU(Reducer("count"), 10, 5, WinType.TB, pardegree=2,
                        fire_on="stream")._make_replica(i).core
             for i in range(2)]
    cores.append(WinSeqTPU(Reducer("count"), 10, 10, WinType.TB,
                           fire_on="stream").make_core())
    assert [type(c) for c in cores] == [VecStreamCore] * 3


# -------------------------------------------------------------------- SlotMap

def test_slot_map_keeps_first_appearance_slots_over_two_levels_and_retain():
    rng = np.random.default_rng(0)
    smap, ref = SlotMap(), {}
    for it in range(120):
        keys = rng.integers(0, 5000 * (1 + it // 20), size=3000)
        for k, s in zip(keys.tolist(), smap.lookup(keys).tolist()):
            assert ref.setdefault(k, len(ref)) == s
        if it % 37 == 36:
            keep = np.flatnonzero(rng.random(smap.n) < 0.5)
            kept = smap.keys[:smap.n][keep].copy()
            smap.retain(keep)
            ref = {int(k): i for i, k in enumerate(kept)}
            assert (smap.lookup(kept) == np.arange(len(kept))).all()
    assert smap.n == len(ref)
    snap = smap.state_snapshot()
    other = SlotMap()
    other.state_restore(snap)
    probe = np.fromiter(ref, dtype=np.int64)
    assert (other.lookup(probe) == smap.lookup(probe)).all()


def test_registration_does_not_touch_the_large_level_every_chunk():
    smap = SlotMap()
    smap.lookup(np.arange(100_000, dtype=np.int64))
    smap._merge()
    large = smap._sorted_keys
    for i in range(20):
        smap.lookup(np.arange(100_000 + 10 * i, 100_010 + 10 * i,
                              dtype=np.int64))
    assert smap._sorted_keys is large and len(smap._new_keys) == 200


# -------------------------------------------------------------- ProgressMerge

class _Out:
    def __init__(self):
        self.items = []

    def put(self, src, batch):
        self.items.append(batch)


def _results(wid, keys, dtype):
    out = np.zeros(len(keys), dtype=dtype)
    out["key"], out["id"], out["ts"] = keys, wid, wid * 5 + 9
    return out


def test_progress_merge_releases_on_the_slowest_channels_progress():
    dtype = VALUE.dtype()
    node, out = ProgressMerge(2), _Out()
    node._outputs = [(out, 0)]
    # channel 0 runs two windows ahead
    for w in (0, 1):
        node.svc(np.concatenate((_results(w, [2, 4], dtype),
                                 progress_row(dtype, w, w * 5 + 10))), 0)
    assert not out.items
    node.svc(np.concatenate((_results(0, [1], dtype),
                             progress_row(dtype, 0, 10))), 1)
    assert [b["id"].tolist() for b in out.items] == [[0, 0], [0], [0]]
    assert out.items[-1][MARKER_FIELD].tolist() == [True]
    assert int(out.items[-1]["ts"][0]) == 10
    # channel 1 jumps two windows at once: what goes, goes in time order
    del out.items[:]
    node.svc(_results(2, [2], dtype), 0)
    node.svc(progress_row(dtype, 2, 20), 0)
    node.svc(np.concatenate((_results(1, [3], dtype), _results(2, [3], dtype),
                             progress_row(dtype, 2, 20))), 1)
    rows = np.concatenate(out.items)
    assert rows["id"].tolist() == [1, 1, 1, 2, 2, 2]
    assert rows[MARKER_FIELD].tolist() == [False] * 5 + [True]
    # a channel at its end holds nobody back
    del out.items[:]
    node.svc(_results(3, [4], dtype), 0)
    node.on_channel_eos(1)
    node.on_channel_eos(0)
    assert np.concatenate(out.items)["id"].tolist() == [3]


# ------------------------------------------------- the two-stage pipeline (Q5)

BIDS = Schema(auction=np.int64)
COUNTS = Schema(auction=np.int64, num=np.int64, bids=np.int64,
                lastUpdate=np.int64)


def _to_counts(rows, out):
    out["key"] = 0
    out["auction"] = rows["key"]
    out["num"] = rows["count"]
    out["bids"] = rows["count"]
    out["lastUpdate"] = rows["lastUpdate"]


def hot_items_pipe(source_fn, sink_fn, pardegree, win, slide, trace_dir=None,
                   farm=KeyFarmTPU):
    per_auction = MultiReducer(Reducer("count", out_field="count"),
                               Reducer("max", "ts", "lastUpdate"))
    hottest = MultiReducer(
        ArgReducer("max", "num", id_field="auction", id_out="auction",
                   value_range=(0, 1 << 20)),
        Reducer("sum", "bids", "bids", value_range=(0, 1 << 20)),
        Reducer("max", "lastUpdate", "lastUpdate", value_range=(0, 1 << 30)))
    return (MultiPipe("q5", trace_dir=trace_dir)
            .add_source(Source(source_fn, BIDS, name="src"))
            .add(farm(per_auction, win, slide, WinType.TB,
                      pardegree=pardegree, fire_on="stream", name="count"))
            .add(Map(_to_counts, vectorized=True, output_schema=COUNTS,
                     name="rekey"))
            .add(WinSeqTPU(hottest, slide, slide, WinType.TB, batch_len=1,
                           flush_rows=4096, name="top"))
            .add_sink(Sink(sink_fn, vectorized=True)))


def bids(seed, n=12000, span=9000, gap=None):
    """Bids on a key space that grows, half of them on the hot auction of
    the moment (a multiple of 100, as NEXMark's)."""
    rng = np.random.default_rng(seed)
    ts = np.sort(rng.integers(0, span, n))
    if gap is not None:                     # a stretch without a bid
        ts = ts[(ts < gap[0]) | (ts >= gap[1])]
    auction = 1000 + ts // 7 + rng.integers(0, 30, len(ts))
    auction = np.where(rng.random(len(ts)) < 0.5, auction // 100 * 100,
                       auction)
    return batch_from_columns(BIDS, key=auction, id=np.arange(len(ts)),
                              ts=ts, auction=auction)


def run_hot_items(rows, chunk, pardegree, win=1000, slide=500, **kw):
    got = []

    def source(shipper):
        for lo in range(0, len(rows), chunk):
            shipper.push_batch(rows[lo:lo + chunk].copy())

    pipe = hot_items_pipe(source, lambda r: got.append(r.copy())
                          if r is not None else None, pardegree, win, slide,
                          **kw)
    pipe.run_and_wait_end(timeout=120)
    out = np.concatenate(got)
    assert not out[MARKER_FIELD].any()        # a sink sees no marker row
    out = out[out["bids"] > 0]
    assert (np.diff(out["id"]) > 0).all()     # window order, each once
    return pipe, {int(r["id"]) - 1: (int(r["auction"]), int(r["num"]),
                                     int(r["bids"]), int(r["lastUpdate"]))
                  for r in out}


@pytest.mark.parametrize("chunk", [257, 3000, 20000])
@pytest.mark.parametrize("pardegree", [1, 4])
def test_hot_items_pipeline_against_the_plain_reference(pardegree, chunk):
    rows = bids(5)
    pipe, got = run_hot_items(rows, chunk, pardegree)
    assert got == hot_items_windows(rows, 1000, 500)
    assert [type(c).__name__ for c in window_cores(pipe._df)] == \
        ["VecStreamCore"] * pardegree + ["NativeResidentCore"]


@pytest.mark.parametrize("farm", [KeyFarmTPU, KeyFarm])
def test_hot_items_pipeline_with_a_window_without_a_bid(farm):
    rows = bids(6, gap=(2100, 4700))
    _pipe, got = run_hot_items(rows, 1500, 4, farm=farm)
    want = hot_items_windows(rows, 1000, 500)
    assert got == want and not {5, 6, 7} & set(want)


def test_hot_items_pipeline_reports_its_keys_and_its_progress(tmp_path):
    rows = bids(8)
    run_hot_items(rows, 900, 4, trace_dir=str(tmp_path))
    logs = {}
    for path in tmp_path.glob("*.log"):
        node = json.loads(path.read_text())
        logs[node["node"].split("_", 2)[2]] = node
    workers = [logs[f"count.{i}"] for i in range(4)]
    windows = len(hot_items_windows(rows, 1000, 500))
    for w in workers:
        assert w["stream_fires"] >= windows - 3
        assert w["progress_sent"] == w["stream_fires"]
        assert w["burst_batches"] >= w["stream_fires"]
        assert 0 < w["keys_live_peak"] < w["keys_retired"]
    assert sum(w["stream_fire_rows"] for w in workers) > len(rows) / 10
    merge = logs["count.collector"]
    assert merge["progress_seen"] == sum(w["progress_sent"] for w in workers)
    assert windows - 3 <= merge["progress_sent"] <= workers[0]["stream_fires"]
    assert logs["top.0"]["progress_seen"] == merge["progress_sent"]


@pytest.mark.parametrize("pardegree", [1, 4])
def test_progress_closes_stage_two_before_the_next_windows_first_row(
        pardegree):
    """The answer of window 0 reaches the sink while the source waits: no
    count of window 1 has left stage 1, only the progress row has crossed
    the Map, the collector and the merge."""
    first = threading.Event()
    seen = []

    def source(shipper):
        ts = np.concatenate((np.arange(0, 1000, 5), np.arange(1000, 1008)))
        key = 1000 + np.arange(len(ts)) % 8
        rows = batch_from_columns(BIDS, key=key, id=np.arange(len(ts)),
                                  ts=ts, auction=key)
        shipper.push_batch(rows)
        seen.append(first.wait(timeout=30))
        shipper.push_batch(batch_from_columns(
            BIDS, key=[1000], id=[len(ts)], ts=[1700], auction=[1000]))

    def sink(rows):
        if rows is not None and (rows["bids"] > 0).any():
            first.set()

    hot_items_pipe(source, sink, pardegree, 1000, 500).run_and_wait_end(
        timeout=120)
    assert seen == [True]
