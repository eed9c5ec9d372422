"""The arg-extremum reducer (ops/functions.ArgReducer): its host forms
against a loop, the NEXMark Q7 pipeline through ``WinMapReduceTPU`` against
the plain reference, the blockwise device evaluation against ``_ring_eval``,
the ring's compaction and growth on the device, and the paths that must
refuse it loudly."""

import time

import numpy as np
import pytest

import jax.numpy as jnp

from oracle import highest_bid_windows
from windflow_tpu.api import MultiPipe
from windflow_tpu.core.tuples import Schema, batch_from_columns
from windflow_tpu.core.windows import WindowSpec, WinType
from windflow_tpu.ops import resident
from windflow_tpu.ops.functions import (NO_ARG_ID, ArgReducer, MultiReducer,
                                        Reducer)
from windflow_tpu.patterns.basic import Filter, Sink, Source
from windflow_tpu.patterns.win_mapreduce import WinMapEmitterNode
from windflow_tpu.patterns.win_seq import WinSeq, window_cores
from windflow_tpu.patterns.win_seq_tpu import (ResidentWinSeqCore,
                                               WinMapReduceTPU, make_core_for)

BID = Schema(event_type=np.int8, auction=np.int64, bidder=np.int64,
             price=np.int64)
PRICE = (0, 100_000_001)
TS = (0, 2_000_000_000)


def _bids(n, seed, span=3500, prices=(100, 100_000), plant=None,
          shuffle_ids=False):
    rng = np.random.default_rng(seed)
    price = rng.integers(*prices, n)
    if plant:
        price[::plant] = prices[1] + 5       # many rows share the maximum
    ids = rng.permutation(n) if shuffle_ids else np.arange(n)
    return batch_from_columns(
        BID, key=np.zeros(n), id=ids, ts=np.sort(rng.integers(0, span, n)),
        event_type=(np.arange(n) % 50 >= 4) * 2, auction=rng.integers(0, 99, n),
        bidder=rng.integers(0, 999, n), price=price)


# -- host forms -------------------------------------------------------------

def _loop(rows, op, field="price", idf="id"):
    best = None
    for r in rows:
        v, i = int(r[field]), int(r[idf])
        if best is None or (v > best[0] if op == "max" else v < best[0]) \
                or (v == best[0] and i < best[1]):
            best = (v, i, int(r["auction"]), int(r["ts"]))
    return best


@pytest.mark.parametrize("shuffle_ids", [False, True])
@pytest.mark.parametrize("prices", [(100, 100_000), (5, 9)])
@pytest.mark.parametrize("op", ["max", "min"])
def test_nic_inc_and_batch_forms_agree_with_a_loop(op, prices, shuffle_ids):
    rows = _bids(300, 4, prices=prices, shuffle_ids=shuffle_ids)
    red = ArgReducer(op, "price", id_out="bid",
                     carry=("auction", ("ts", "when")), value_range=(0, 10**6))
    want = _loop(rows, op)
    assert tuple(int(v) for v in red.apply(0, 0, rows)) == want
    acc = red.init(0, 0)
    for r in rows:
        red.update(0, 0, r, acc)
    assert tuple(int(acc[f]) for f in red.result_fields) == want
    acc = red.init(0, 0)
    for part in np.array_split(rows, 7):
        red.update_many(0, 0, part, acc)
    assert tuple(int(acc[f]) for f in red.result_fields) == want
    # batched: three windows of unequal length, padded, one of them empty
    lens = np.array([300, 0, 117])
    cols = {f: np.stack([rows[f], np.zeros(300, rows[f].dtype),
                         np.pad(rows[f][:117], (0, 183))])
            for f in red.required_fields}
    got = red.apply_batch(None, None, cols, lens)
    for i, part in enumerate((rows, rows[:0], rows[:117])):
        assert tuple(int(got[f][i]) for f in red.result_fields) == \
            tuple(int(v) for v in red.apply(0, 0, part))


def test_an_empty_window_gives_the_identity_outside_the_declared_range():
    rows = _bids(0, 0)
    assert ArgReducer("max", "price", id_out="bid", value_range=(0, 50)) \
        .apply(0, 0, rows) == (-1, NO_ARG_ID)
    assert ArgReducer("min", "price", value_range=(0, 50)) \
        .apply(0, 0, rows) == (50,)
    assert ArgReducer("max", "price").apply(0, 0, rows) == \
        (np.iinfo(np.int64).min,)
    with pytest.raises(ValueError):
        ArgReducer("sum", "price")
    with pytest.raises(ValueError):
        ArgReducer("max", "price", carry=("price",))


# -- the Q7 pipeline --------------------------------------------------------

def _q7_functions():
    map_fn = MultiReducer(
        ArgReducer("max", "price", id_out="bid",
                   carry=("auction", "bidder", ("ts", "dateTime")),
                   value_range=PRICE),
        Reducer("count", out_field="count"),
        Reducer("max", "ts", "lastUpdate", value_range=TS))
    reduce_fn = MultiReducer(
        ArgReducer("max", "price", id_field="bid",
                   carry=("auction", "bidder", "dateTime"),
                   value_range=PRICE),
        Reducer("sum", "count", "count", value_range=(0, 500_000_000)),
        Reducer("max", "lastUpdate", "lastUpdate", value_range=TS))
    return map_fn, reduce_fn


@pytest.mark.parametrize("plant", [None, 97])
@pytest.mark.parametrize("map_degree", [2, 3, 4])
def test_q7_through_win_mapreduce_equals_the_plain_reference(map_degree,
                                                             plant):
    """Three windows of a few thousand bids, persons and auctions filtered
    out in front; with ties planted every MAP worker holds the maximum
    several times, so the lowest id has to win in MAP and in REDUCE."""
    win = 1000
    events = _bids(12000, map_degree + (plant or 0), span=3 * win,
                   plant=plant)
    got = []
    map_fn, reduce_fn = _q7_functions()

    def src(shipper):
        for i in range(0, len(events), 1777):
            shipper.push_batch(events[i:i + 1777].copy())

    pipe = (MultiPipe("q7")
            .add_source(Source(src, BID, name="src"))
            .chain(Filter(lambda b: b["event_type"] == 2, vectorized=True,
                          name="bids"))
            .add(WinMapReduceTPU(map_fn, reduce_fn, win, win, WinType.TB,
                                 map_degree=map_degree, reduce_degree=1,
                                 map_on_device=True, reduce_on_device=True,
                                 batch_len=1, flush_rows=512, name="wmr"))
            .chain_sink(Sink(lambda r: got.append(r.copy())
                             if r is not None and len(r) else None,
                             vectorized=True, name="sink")))
    pipe.run_and_wait_end()
    cores = window_cores(pipe._df)
    assert [type(c).__name__ for c in cores] == \
        ["NativeResidentCore"] * (map_degree + 1)
    for c in cores:
        assert type(c.executor).__name__ == "ArgExtResidentExecutor"
        assert c.executor.dispatches > 0 and c._delegate is None
    res = np.concatenate(got)
    want = highest_bid_windows(events[events["event_type"] == 2], win)
    assert list(res["id"]) == sorted(want)              # in window order
    for r in res:
        assert (int(r["price"]), int(r["auction"]), int(r["bidder"]),
                int(r["dateTime"]), int(r["count"]),
                int(r["lastUpdate"])) == want[int(r["id"])]


# -- the device evaluation --------------------------------------------------

@pytest.mark.parametrize("n_windows", [1, 9])
@pytest.mark.parametrize("op", ["max", "min"])
def test_blockwise_evaluation_equals_the_gather(op, n_windows):
    """Windows shorter than one evaluation block, longer than several and
    straddling block edges, one and nine to a launch (Bb 8 and 16), against
    the masked (Bb, pad) gather of ``_ring_eval``."""
    cap, eb, KP = 1024, 64, 2
    rng = np.random.default_rng(n_windows)
    ring = jnp.asarray(rng.integers(-50, 50, (KP, cap)), dtype=jnp.int32)
    Bb = 8 if n_windows == 1 else 16
    starts = rng.integers(0, 300, Bb).astype(np.int32)
    lens = np.array(([700] if n_windows == 1 else
                     [5, 63, 64, 65, 200, 700, 1, 0, 129])
                    + [0] * (Bb - n_windows), dtype=np.int32)
    rows = rng.integers(0, KP, Bb).astype(np.int32)
    ext, first, n = resident._ring_extremum(
        op, cap, eb, np.dtype(np.int32), ring, rows, starts, lens)
    want = resident._ring_eval(op, cap, 1024, np.dtype(np.int32), ring,
                               rows, starts, lens)
    assert np.array_equal(np.asarray(ext), np.asarray(want))
    host = np.asarray(ring)
    for i in range(Bb):
        seg = host[rows[i], starts[i]:starts[i] + lens[i]]
        if not len(seg):
            assert int(n[i]) == 0
            continue
        hit = np.flatnonzero(seg == np.asarray(want)[i])
        assert (int(first[i]), int(n[i])) == (int(hit[0]), len(hit))


@pytest.mark.parametrize("win,slide,wt,shuffle_ids", [
    (500, 500, WinType.CB, False), (250, 250, WinType.TB, True),
    (900, 300, WinType.CB, False)])
def test_the_ring_compacts_and_grows_on_the_device(win, slide, wt,
                                                   shuffle_ids, monkeypatch):
    """A ring far smaller than the stream: the core slides live rows to the
    front on the device (and widens the ring when harvests lag), the result
    stays the host core's."""
    monkeypatch.setenv("WF_NO_OVERLAP", "1")    # ship on this thread
    n = 30000
    rng = np.random.default_rng(win)
    sch = Schema(price=np.int64, auction=np.int64)
    b = batch_from_columns(
        sch, key=np.zeros(n),
        id=rng.permutation(n) if shuffle_ids else np.arange(n),
        ts=np.sort(rng.integers(0, n // 2, n)),
        price=rng.integers(0, 60, n), auction=rng.integers(0, 99, n))

    def fn():
        return MultiReducer(
            ArgReducer("min", "price", id_out="bid",
                       carry=("auction", ("ts", "when")),
                       value_range=(0, 1000)),
            Reducer("count", out_field="count"))

    core = make_core_for(WindowSpec(win, slide, wt), fn(), batch_len=1,
                         flush_rows=64)
    outs = []
    for i in range(0, n, 333):
        outs.append(core.process(b[i:i + 333]))
        _wait_harvests(core)       # harvests keep pace: the ring stays small
    got = np.concatenate(outs + [core.flush()])
    host = WinSeq(fn(), win, slide, wt).make_core()
    want = np.concatenate([host.process(b), host.flush()])
    assert np.array_equal(got, want)
    assert core.executor.cap <= 8192 < n    # the stream never fitted the ring


def _wait_harvests(core):
    deadline = time.monotonic() + 30
    ex = core.executor
    while time.monotonic() < deadline and any(
            not ex._is_ready(e[2]) for e in list(ex._inflight)):
        time.sleep(0.001)


@pytest.mark.parametrize("wt", [WinType.TB, WinType.CB])
@pytest.mark.parametrize("sibling", [("sum", "auction"), ("max", "bidder"),
                                     ("min", "price")])
@pytest.mark.parametrize("arg_at", [0, 1, 2])
def test_ties_are_resolved_on_the_arg_field_wherever_it_stands(arg_at,
                                                               sibling, wt):
    """The arg-extremum first, second or last among the device stats, beside
    a sibling over another field (or another op over its own): with ids
    shuffled and the maximum planted many times a window, the lowest id at
    the *price* wins -- the tie scan reads the arg stat's archive column, not
    ship field 0."""
    n, win = 6000, 500
    rows = _bids(n, 11 + arg_at, span=12 * win, prices=(100, 400), plant=7,
                 shuffle_ids=wt is WinType.TB)

    def fn():
        parts = [Reducer(sibling[0], sibling[1], "sib",
                         value_range=(0, 10**6)),
                 Reducer("count", out_field="count")]
        parts.insert(arg_at, ArgReducer(
            "max", "price", id_out="bid", carry=("auction", "bidder"),
            value_range=(0, 1000)))
        return MultiReducer(*parts)

    core = make_core_for(WindowSpec(win, win, wt), fn(), batch_len=1,
                         flush_rows=256)
    assert type(core.executor).__name__ == "ArgExtResidentExecutor"
    outs = []
    for i in range(0, n, 700):
        outs.append(core.process(rows[i:i + 700]))
        _wait_harvests(core)
    got = np.concatenate(outs + [core.flush()])
    host = WinSeq(fn(), win, win, wt).make_core()
    want = np.concatenate([host.process(rows), host.flush()])
    assert np.array_equal(got, want)
    # every window tied, and a brute-force pick agrees on the winner's id
    pos = rows["ts"] if wt is WinType.TB else rows["id"]
    for r in got[got["count"] > 0]:
        w = rows[(pos >= r["id"] * win) & (pos < (r["id"] + 1) * win)]
        top = w[w["price"] == w["price"].max()]
        assert len(top) > 1 and int(r["bid"]) == int(top["id"].min())


@pytest.mark.parametrize("declared", [None, 50000, 200000])
def test_a_declared_window_sizes_the_ring_up_front(declared, monkeypatch):
    """A time-based window's rows are not in its spec.  Declared on the
    reducer (``window_rows``), the ring starts twice as wide as one window
    and a rectangle and never grows under a stream that keeps to it, the
    same in every core built from the declaration; undeclared, the ring
    grows into the stream.  The results are the same either way."""
    monkeypatch.setenv("WF_NO_OVERLAP", "1")
    win, flush = 50000, 1024
    n = 2 * win + 5000
    b = batch_from_columns(Schema(price=np.int64), key=np.zeros(n),
                           id=np.arange(n), ts=np.arange(n),
                           price=np.arange(n) % 97)
    caps = []
    for _again in range(2):
        core = make_core_for(
            WindowSpec(win, win, WinType.TB),
            ArgReducer("max", "price", value_range=(0, 100),
                       window_rows=declared), batch_len=1, flush_rows=flush)
        seen, outs = [], []
        for i in range(0, n, 4 * flush):
            outs.append(core.process(b[i:i + 4 * flush]))
            _wait_harvests(core)
            seen.append(core.executor.cap)
        res = np.concatenate(outs + [core.flush()])
        assert list(res["price"]) == [96, 96, 96]
        caps.append(sorted(set(seen)))
    assert caps[0] == caps[1]
    if declared is None:
        assert len(caps[0]) > 1             # grew into its first window
    else:
        want = 1
        while want < 2 * (declared + flush):
            want *= 2
        assert caps[0] == [want]            # sized once, never grown
    with pytest.raises(ValueError, match="window_rows"):
        ArgReducer("max", "price", window_rows=0)


@pytest.mark.parametrize("wt", [WinType.TB, WinType.CB], ids=["tb", "cb"])
def test_the_winning_rows_own_ts_is_read_back(wt):
    """The ts the result carries is the winning row's own.  On a time-based
    window that is the row's position, and the native archive keeps no ts
    column; on a count-based one it is anything the row says (here: in no
    order at all), the one case the archive keeps the column for."""
    n, win = 4000, 250
    rows = _bids(n, 21, span=16 * win, prices=(100, 400), plant=9)
    if wt is WinType.CB:
        rows["ts"] = np.random.default_rng(3).integers(0, 10**6, n)

    def fn():
        return MultiReducer(
            ArgReducer("max", "price", id_out="bid", carry=(("ts", "when"),),
                       value_range=(0, 1000)),
            Reducer("count", out_field="count"))

    core = make_core_for(WindowSpec(win, win, wt), fn(), batch_len=1,
                         flush_rows=256)
    # position, price, tie-break id -- and ts on the count-based core alone
    assert core.archive_row_bytes == (32 if wt is WinType.CB else 24)
    outs = []
    for i in range(0, n, 700):
        outs.append(core.process(rows[i:i + 700]))
        _wait_harvests(core)
    got = np.concatenate(outs + [core.flush()])
    host = WinSeq(fn(), win, win, wt).make_core()
    assert np.array_equal(
        got, np.concatenate([host.process(rows), host.flush()]))
    pos = rows["ts"] if wt is WinType.TB else rows["id"]
    full = got[got["count"] > 0]
    assert len(full) >= 15
    for r in full:
        w = rows[(pos >= r["id"] * win) & (pos < (r["id"] + 1) * win)]
        _v, bid, _a, when = _loop(w, "max")
        assert (int(r["bid"]), int(r["when"])) == (bid, when)


@pytest.mark.parametrize("kind,n_fields,n_carry,arg,want", [
    ("cb", 1, 0, False, 16), ("cb", 1, 0, True, 24), ("tb", 1, 0, True, 16),
    ("cb", 2, 0, False, 24), ("cb", 1, 2, True, 40), ("tb", 2, 3, True, 48)])
def test_archive_row_bytes_by_the_cores_own_arguments(kind, n_fields, n_carry,
                                                      arg, want):
    """8 bytes a column: the position, each shipped field, each carried
    column, and ts where the core is a count-based arg-extremum -- which it
    knows from how it was configured, before its first row."""
    import ctypes
    from windflow_tpu import native
    lib = native.load()
    h = lib.wf_core_new(8, 8, 0 if kind == "cb" else 1, 0, 0, 1, 8, 0, 1, 8,
                        0, 1, 8, 64, 256, 2)
    try:
        wires = (ctypes.c_int * n_fields)(*[2] * n_fields)
        assert lib.wf_core_set_fields(h, n_fields, wires) == n_fields
        if arg:
            assert lib.wf_core_set_arg(h, n_carry, 0, 0) == n_carry
        assert lib.wf_core_archive_row_bytes(h) == want
    finally:
        lib.wf_core_free(h)


# -- refusals ---------------------------------------------------------------

def test_a_path_that_cannot_run_it_refuses_loudly(monkeypatch):
    spec = WindowSpec(8, 8, WinType.CB)
    red = ArgReducer("max", "price", value_range=PRICE)
    with pytest.raises(ValueError, match="cannot run on the device"):
        make_core_for(spec, red, use_resident=False)
    with pytest.raises(ValueError, match="one shard"):
        make_core_for(spec, red, shards=2)
    with pytest.raises(ValueError, match="sibling stats"):
        make_core_for(spec, MultiReducer(
            red, Reducer("sum", "auction", dtype=np.float32)))
    with pytest.raises(TypeError):
        ResidentWinSeqCore(spec, red)
    monkeypatch.setenv("WF_NO_NATIVE_CORE", "1")
    with pytest.raises(ValueError, match="native resident core"):
        make_core_for(spec, red)
    monkeypatch.delenv("WF_NO_NATIVE_CORE")
    # a payload the native ABI cannot stage (int32 column): no Python route
    core = make_core_for(spec, red)
    sch = Schema(price=np.int32)
    with pytest.raises(TypeError, match="native resident core only"):
        core.process(batch_from_columns(sch, key=[0], id=[0], ts=[0],
                                        price=[1]))


# -- the Win_MapReduce emitter's split ---------------------------------------

class _Catch(WinMapEmitterNode):
    def __init__(self, n):
        super().__init__(n, WinType.CB)
        self.sent = []

    def emit_to(self, d, batch):
        self.sent.append((d, batch))


@pytest.mark.parametrize("n_keys", [1, 5])
@pytest.mark.parametrize("degree", [2, 3, 4])
def test_the_round_robin_split_is_the_boolean_subscripts(degree, n_keys):
    """Byte for byte what ``batch[dest == d]`` gave, in owned arrays, for a
    key-less stream (the strided fast path) and a keyed one."""
    rng = np.random.default_rng(degree)
    em, nxt, seen = _Catch(degree), {}, {}
    for _chunk in range(3):
        n = 257
        keys = rng.integers(0, n_keys, n)
        ids = np.zeros(n, dtype=np.int64)
        for k in range(n_keys):         # per-key ids run on across chunks
            m = keys == k
            ids[m] = seen.get(k, 0) + np.arange(m.sum())
            seen[k] = seen.get(k, 0) + int(m.sum())
        b = batch_from_columns(BID, key=keys, id=ids, ts=ids,
                               event_type=2, auction=rng.integers(0, 9, n),
                               bidder=rng.integers(0, 9, n),
                               price=rng.integers(0, 9, n))
        dest = np.empty(n, dtype=np.int64)
        for i, k in enumerate(keys):
            dest[i] = nxt.get(int(k), int(k) % degree)
            nxt[int(k)] = (dest[i] + 1) % degree
        em.sent = []
        before = b.tobytes()
        em.svc(b)
        assert [d for d, _s in em.sent] == \
            [d for d in range(degree) if (dest == d).any()]
        for d, sub in em.sent:
            assert sub.tobytes() == b[dest == d].tobytes()
            assert sub.flags.owndata and not np.shares_memory(sub, b)
        assert b.tobytes() == before
