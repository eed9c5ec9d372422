"""Differential farm tests — the equivalent of src/sum_test_cpu's
test_{wf,kf}_{cb,tb}_{nic,inc} plus the test_all differential harness:
every farm composition must produce the SAME per-key ordered results as
the sequential Win_Seq on the same stream."""

import numpy as np
import pytest

from windflow_tpu.core.tuples import Schema, batch_from_columns
from windflow_tpu.core.windows import WinType
from windflow_tpu.ops.functions import Reducer
from windflow_tpu.patterns.basic import Sink, Source
from windflow_tpu.patterns.key_farm import KeyFarm
from windflow_tpu.patterns.win_farm import WinFarm
from windflow_tpu.patterns.win_seq import WinSeq
from windflow_tpu.runtime.engine import Dataflow
from windflow_tpu.runtime.farm import build_pipeline

SCHEMA = Schema(value=np.int64)


def cb_stream_batches(keys, n, chunk=32):
    out = []
    for i in range(0, n, chunk):
        ids = np.arange(i, min(i + chunk, n))
        ids = np.repeat(ids, keys)
        ks = np.tile(np.arange(keys), len(ids) // keys)
        out.append(batch_from_columns(SCHEMA, key=ks, id=ids, ts=ids * 7,
                                      value=ids))
    return out


def tb_stream_batches(keys, n, chunk=32, seed=0):
    rng = np.random.default_rng(seed)
    rows = []
    for k in range(keys):
        ts = 0
        for i in range(n):
            ts += int(rng.integers(0, 9))
            rows.append((k, i, ts, i))
    rows.sort(key=lambda r: r[2])
    out = []
    for i in range(0, len(rows), chunk):
        part = rows[i:i + chunk]
        out.append(batch_from_columns(
            SCHEMA, key=[r[0] for r in part], id=[r[1] for r in part],
            ts=[r[2] for r in part], value=[r[3] for r in part]))
    return out


def run_windowed(pattern, batches, graph=None, trace_dir=None):
    """Run Source -> pattern -> Sink; returns per-key ordered results.
    ``graph``: a list the built Dataflow is appended to."""
    per_key = {}

    def snk(row):
        if row is not None:
            per_key.setdefault(int(row["key"]), []).append(
                (int(row["id"]), int(row["ts"]), int(row["value"])))

    df = Dataflow(trace_dir=trace_dir)
    build_pipeline(df, [Source(batches=iter(batches), schema=SCHEMA),
                        pattern, Sink(snk)])
    df.run_and_wait_end()
    if graph is not None:
        graph.append(df)
    return per_key


def dense_fire_counts(df) -> list:
    """``windows_fired_complete`` of every window core of ``df`` that was
    told its input is dense (a Pane_Farm's WLQ cores): the windows each
    fired with their last pane and not with the next one."""
    from windflow_tpu.patterns.win_seq import window_cores
    counts = [getattr(c, "windows_fired_complete", None)
              for c in window_cores(df)]
    return [c for c in counts if c is not None]


def complete_windows(results, batches, win, slide, wt) -> int:
    """How many of a Pane_Farm's ``results`` (per key, ``(id, ...)``) are
    windows whose every pane exists: a key's panes run to the one that
    holds its last row, whoever emits that one (the PLQ's flush too)."""
    import math
    pane = math.gcd(win, slide)
    rows = np.concatenate(batches)
    pos = rows["id" if wt is WinType.CB else "ts"]
    n = 0
    for key, rs in results.items():
        panes = int(pos[rows["key"] == key].max()) // pane + 1
        n += sum(1 for r in rs if (r[0] * slide + win) // pane <= panes)
    return n


def assert_wlq_fired_complete(df, results, batches, win, slide, wt,
                              wlq_degree=None):
    """Every window of ``results`` that was complete at its WLQ was fired
    by its last pane's arrival; the flush is left with the others."""
    counts = dense_fire_counts(df)
    if wlq_degree is not None:
        assert len(counts) == wlq_degree
    want = complete_windows(results, batches, win, slide, wt)
    assert sum(counts) == want > 0
    assert want >= sum(len(rs) for rs in results.values()) \
        - len(results) * -(-win // slide)


CASES = [(8, 3), (8, 8), (3, 8), (5, 1), (16, 7)]


@pytest.mark.parametrize("win,slide", CASES)
@pytest.mark.parametrize("pardegree", [2, 3, 5])
@pytest.mark.parametrize("inc", [False, True])
def test_win_farm_cb_matches_seq(win, slide, pardegree, inc):
    keys, n = 3, 120
    ref = run_windowed(
        WinSeq(Reducer("sum"), win, slide, WinType.CB, incremental=inc),
        cb_stream_batches(keys, n))
    got = run_windowed(
        WinFarm(Reducer("sum"), win, slide, WinType.CB, pardegree=pardegree,
                incremental=inc),
        cb_stream_batches(keys, n))
    assert got == ref


@pytest.mark.parametrize("win,slide", [(40, 15), (30, 30), (15, 40)])
@pytest.mark.parametrize("pardegree", [2, 4])
def test_win_farm_tb_matches_seq(win, slide, pardegree):
    keys, n = 2, 150
    ref = run_windowed(WinSeq(Reducer("sum"), win, slide, WinType.TB),
                       tb_stream_batches(keys, n))
    got = run_windowed(
        WinFarm(Reducer("sum"), win, slide, WinType.TB, pardegree=pardegree),
        tb_stream_batches(keys, n))
    assert got == ref


@pytest.mark.parametrize("win,slide", CASES)
@pytest.mark.parametrize("pardegree", [2, 4])
@pytest.mark.parametrize("inc", [False, True])
def test_key_farm_cb_matches_seq(win, slide, pardegree, inc):
    keys, n = 5, 100
    ref = run_windowed(
        WinSeq(Reducer("sum"), win, slide, WinType.CB, incremental=inc),
        cb_stream_batches(keys, n))
    got = run_windowed(
        KeyFarm(Reducer("sum"), win, slide, WinType.CB, pardegree=pardegree,
                incremental=inc),
        cb_stream_batches(keys, n))
    assert got == ref


@pytest.mark.parametrize("pardegree", [2, 3])
def test_key_farm_tb_matches_seq(pardegree):
    keys, n = 4, 120
    ref = run_windowed(WinSeq(Reducer("sum"), 25, 10, WinType.TB),
                       tb_stream_batches(keys, n))
    got = run_windowed(
        KeyFarm(Reducer("sum"), 25, 10, WinType.TB, pardegree=pardegree),
        tb_stream_batches(keys, n))
    assert got == ref


def test_win_farm_ordered_collector_dense_ids():
    """Ordered collector delivers result ids 0,1,2,... per key (the
    Consumer check, sum_cb.hpp:146-150)."""
    got = run_windowed(
        WinFarm(Reducer("sum"), 10, 5, WinType.CB, pardegree=4),
        cb_stream_batches(2, 200))
    for rs in got.values():
        assert [r[0] for r in rs] == list(range(len(rs)))


def test_win_farm_unordered_same_multiset():
    ref = run_windowed(WinSeq(Reducer("sum"), 10, 5, WinType.CB),
                       cb_stream_batches(2, 150))
    got = run_windowed(
        WinFarm(Reducer("sum"), 10, 5, WinType.CB, pardegree=3, ordered=False),
        cb_stream_batches(2, 150))
    for k in ref:
        assert sorted(got[k]) == sorted(ref[k])


def test_ordering_core_kway_merge():
    """OrderingCore releases rows only once all channels' watermarks pass,
    and flushes markers last."""
    from windflow_tpu.runtime.ordering import OrderingCore, OrderingMode

    oc = OrderingCore(2, OrderingMode.ID)
    b1 = batch_from_columns(SCHEMA, key=[0, 0], id=[0, 2], ts=[0, 2],
                            value=[0, 2])
    b2 = batch_from_columns(SCHEMA, key=[0, 0], id=[1, 3], ts=[1, 3],
                            value=[1, 3])
    out1 = oc.push(b1, 0)      # channel-1 watermark still -inf -> nothing
    assert out1 == []
    out2 = oc.push(b2, 1)      # min watermark now min(2,3)=2 -> 0,1,2 out
    released = np.concatenate(out2)["id"].tolist()
    assert released == [0, 1, 2]
    rest = [r["id"][0] for r in oc.flush()]
    assert rest == [3]


def test_ordering_renumbering():
    from windflow_tpu.runtime.ordering import OrderingCore, OrderingMode

    oc = OrderingCore(2, OrderingMode.TS_RENUMBERING)
    b1 = batch_from_columns(SCHEMA, key=[0, 0], id=[40, 41], ts=[10, 30],
                            value=[0, 0])
    b2 = batch_from_columns(SCHEMA, key=[0, 0], id=[90, 91], ts=[20, 40],
                            value=[0, 0])
    oc.push(b1, 0)
    outs = oc.push(b2, 1) + oc.flush()
    merged = np.concatenate(outs)
    assert merged["ts"].tolist() == [10, 20, 30, 40]   # ts-ordered
    assert merged["id"].tolist() == [0, 1, 2, 3]       # densely renumbered


# ------------------------------------------------ StandardEmitter keyed split

class _Tap:
    """An inbox that keeps what it is handed, in arrival order."""

    def __init__(self):
        self.got = []

    def put(self, src, batch):
        self.got.append(batch)


def _keyed_emitter(n_dest, traced):
    from windflow_tpu.runtime.emitters import StandardEmitter, default_routing
    from windflow_tpu.utils.tracing import NodeStats

    em = StandardEmitter(n_dest, default_routing, name="em")
    taps = [_Tap() for _ in range(n_dest)]
    em._outputs = [(t, 0) for t in taps]
    if traced:
        em.stats = NodeStats("em")
    return em, taps


def _split_stream(n_dest, seed):
    """Mixed batches, two whose keys all route to one destination, and an
    empty one."""
    rng = np.random.default_rng(seed)
    out = []
    for i in range(6):
        n = int(rng.integers(50, 400))
        keys = rng.integers(0, 23, n)
        if i in (2, 4):
            keys = keys * n_dest + (i % n_dest)      # one destination
        ids = np.arange(n) + 1000 * i
        out.append(batch_from_columns(SCHEMA, key=keys, id=ids, ts=ids,
                                      value=rng.integers(0, 99, n)))
    out.append(batch_from_columns(SCHEMA, key=[], id=[], ts=[], value=[]))
    return out


@pytest.mark.parametrize("traced", [False, True])
@pytest.mark.parametrize("n_dest", [2, 3, 4])
def test_standard_emitter_keyed_split_is_the_boolean_mask(n_dest, traced):
    """Every destination gets exactly ``batch[dest == d]`` of every batch,
    in arrival order, each piece an array of its own; a batch whose keys
    all route one way is forwarded whole (the same object); the counters
    read what was pushed when tracing is on and do not exist when off."""
    em, taps = _keyed_emitter(n_dest, traced)
    stream = _split_stream(n_dest, seed=n_dest)
    want = [[] for _ in range(n_dest)]
    n_single = n_split = 0
    for b in stream:
        before = b.tobytes()
        em.svc(b)
        assert b.tobytes() == before
        dest = b["key"] % n_dest
        hit = np.unique(dest)
        if len(hit) == 1:
            n_single += 1
            assert taps[int(hit[0])].got[-1] is b
        elif len(hit):
            n_split += 1
        for d in hit:
            want[int(d)].append(b[dest == d])
    assert (n_single, n_split) == (2, 4)
    for d in range(n_dest):
        assert len(taps[d].got) == len(want[d])
        for got, ref in zip(taps[d].got, want[d]):
            assert got.dtype == ref.dtype
            assert got.tobytes() == ref.tobytes()
            assert got.flags.c_contiguous and got.flags.writeable
            assert not any(np.shares_memory(got, b) for b in stream
                           if got is not b)
    if traced:
        snap = em.stats.snapshot()
        assert snap["single_dest_batches"] == n_single
        assert snap["split_batches"] == n_split
    else:
        assert em.stats is None


def test_filter_counts_rows_in_and_out_when_traced():
    from windflow_tpu.patterns.basic import _FilterNode
    from windflow_tpu.utils.tracing import NodeStats

    b = batch_from_columns(SCHEMA, key=np.arange(40) % 4, id=np.arange(40),
                           ts=np.arange(40), value=np.arange(40))
    for traced in (False, True):
        node = _FilterNode(lambda x: x["value"] % 5 != 0, "f", False, True)
        tap = _Tap()
        node._outputs = [(tap, 0)]
        if traced:
            node.stats = NodeStats("f")
        node.svc(b)
        node.svc(b[:0])
        node.svc(b[b["value"] % 5 == 0])           # nothing survives
        assert len(tap.got) == 1
        assert tap.got[0].tobytes() == b[b["value"] % 5 != 0].tobytes()
        assert tap.got[0].flags.owndata
        if traced:
            snap = node.stats.snapshot()
            assert snap["filter_rows_in"] == 48
            assert snap["filter_rows_out"] == 32
        else:
            assert node.stats is None


# ------------------------------------------- the farm emitter's progress rows

def _farm_emitter(win, slide, wt, n):
    from windflow_tpu.core.windows import WindowSpec
    from windflow_tpu.patterns.win_farm import WFEmitterNode
    from windflow_tpu.utils.tracing import NodeStats

    em = WFEmitterNode(WindowSpec(win, slide, wt), n, name="em")
    taps = [_Tap() for _ in range(n)]
    em._outputs = [(t, 0) for t in taps]
    em.stats = NodeStats("em")
    return em, taps


def _named_by_the_rule(pos_before, pos_after, key, win, slide, n):
    """The workers that must hear of ``key``'s move from ``pos_before`` to
    ``pos_after``, window by window: those that own a window which ended
    in ``(pos_before, pos_after]`` less those whose own window holds
    ``pos_after`` (they were sent that row)."""
    def owner(w):
        return (key % n + w) % n

    ended = {owner(w) for w in range(pos_after // slide + 1)
             if pos_before < w * slide + win <= pos_after}
    holding = {owner(w) for w in range(pos_after // slide + 1)
               if w * slide <= pos_after < w * slide + win}
    return ended - holding


@pytest.mark.parametrize("chunk", [1, 7, 1000])
@pytest.mark.parametrize("keys", [1, 3])
@pytest.mark.parametrize("n", [2, 3])
@pytest.mark.parametrize("win,slide", [(30, 30), (15, 40)])
def test_farm_emitter_sends_a_progress_row_where_the_rule_names_a_worker(
        win, slide, n, keys, chunk):
    """Time-based tumbling and hopping windows: when a batch takes a key
    past a window's end, exactly the workers that own such a window and
    were not sent the key's newest row get that row as a marker, behind
    the batch's own rows; a batch that passes no end sends none."""
    from windflow_tpu.core.tuples import MARKER_FIELD

    em, taps = _farm_emitter(win, slide, WinType.TB, n)
    last = {}
    sent = quiet = 0
    for b in tb_stream_batches(keys, 120, chunk=chunk, seed=chunk + keys):
        seen = [len(t.got) for t in taps]
        em.svc(b)
        want = {}                       # worker -> {key: position}
        for key in np.unique(b["key"]).tolist():
            after = int(b["ts"][b["key"] == key].max())
            before = last.get(key, -1)
            last[key] = after
            for d in _named_by_the_rule(before, after, key, win, slide, n):
                want.setdefault(d, {})[key] = after
        quiet += not want
        for d, tap in enumerate(taps):
            new = tap.got[seen[d]:]
            marks = [x for x in new if x[MARKER_FIELD].any()]
            # a worker's rows of the batch first, then its one marker batch
            assert marks == new[len(new) - len(marks):] and len(marks) <= 1
            got = ({int(r["key"]): int(r["ts"]) for r in marks[0]}
                   if marks else {})
            assert got == want.get(d, {})
            if marks:
                assert marks[0][MARKER_FIELD].all()
                sent += len(marks[0])
    assert sent >= (1 if chunk == 1000 else 10)
    assert quiet >= 50 or chunk > 1
    assert em.stats.snapshot()["progress_sent"] == sent


@pytest.mark.parametrize("win,slide,wt", [
    (40, 15, WinType.TB),       # sliding: every row to every worker
    (8, 8, WinType.CB), (3, 8, WinType.CB), (8, 3, WinType.CB)])
def test_farm_emitter_sends_none_on_the_multicast_branch_nor_for_cb_windows(
        win, slide, wt):
    """Where every worker gets every row nobody waits, and a marker in
    mid-stream would overwrite a count-based window's result ts: the only
    markers such an emitter sends are the end-of-stream replay's."""
    from windflow_tpu.core.tuples import MARKER_FIELD

    em, taps = _farm_emitter(win, slide, wt, 2)
    stream = (tb_stream_batches(2, 150, chunk=7) if wt is WinType.TB
              else cb_stream_batches(2, 150, chunk=7))
    for b in stream[6:] if wt is WinType.TB else stream:
        em.svc(b)
    assert sum(len(t.got) for t in taps) > 40
    assert not any(x[MARKER_FIELD].any() for t in taps for x in t.got)
    assert "progress_sent" not in em.stats.snapshot()
    em.eosnotify()
    assert all(t.got[-1][MARKER_FIELD].all() for t in taps)
