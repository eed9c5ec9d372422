"""A Filter in front of a key-by hands its selection on
(core/tuples.Selection; runtime/node.py ``takes_selection`` /
``emit_selection``): the emitter splits the survivors straight out of the
Filter's input, one copy a row.  The array path — gather at the Filter,
split the gathered array — is the reference: what every destination
receives is equal to it as arrays and in batch count, the flag is on only
on the edge the wiring proved, and the row counts read what they read."""

import json

import numpy as np
import pytest

from windflow_tpu.api import MultiPipe
from windflow_tpu.core.tuples import (MARKER_FIELD, Schema, Selection,
                                      batch_from_columns, select_rows)
from windflow_tpu.core.windows import WinType
from windflow_tpu.ops.functions import ArgReducer, MultiReducer, Reducer
from windflow_tpu.patterns.basic import (Filter, Map, Sink, Source,
                                         _FilterNode)
from windflow_tpu.patterns.key_farm import KeyFarm
from windflow_tpu.patterns.win_farm import WinFarm
from windflow_tpu.patterns.win_mapreduce import WinMapReduce
from windflow_tpu.patterns.win_seq_tpu import KeyFarmTPU, WinSeqTPU
from windflow_tpu.runtime.comb import Comb
from windflow_tpu.runtime.emitters import StandardEmitter, default_routing
from windflow_tpu.runtime.engine import Dataflow
from windflow_tpu.utils.tracing import NodeStats

from test_farms import _Tap
from test_stream_fire import COUNTS, _to_counts

#: a 33-byte packed record (pipe_cb's) and a 100-byte one (NEXMark's bid)
SCHEMAS = {
    33: Schema(value=np.int64),
    100: Schema(event_type=np.int8, auction=np.int64, bidder=np.int64,
                price=np.int64, extra=np.dtype((np.uint8, (50,)))),
}


def _stream(width, seed, n_batches=5, markers=False):
    """Batches of random bytes under real headers; with ``markers`` a few
    rows of each carry the marker flag (they are rows like any other to a
    Filter and an emitter)."""
    dtype = SCHEMAS[width].dtype()
    assert dtype.itemsize == width
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n_batches):
        n = int(rng.integers(60, 500))
        b = rng.integers(0, 256, n * width, dtype=np.uint8).view(dtype).copy()
        b["key"] = rng.integers(0, 37, n)
        b["id"] = np.arange(n) + 1000 * i
        b["ts"] = b["id"]
        b[MARKER_FIELD] = (rng.random(n) < 0.05) if markers else False
        out.append(b)
    return out


def _predicate(share, seed):
    """A vectorised predicate that passes ``share`` of the rows (exactly
    none at 0, every one at 1), the same rows on every call for a batch."""
    def fn(b):
        if share in (0, 1):
            return np.full(len(b), bool(share))
        rng = np.random.default_rng([seed, int(b["id"][0]), len(b)])
        return rng.random(len(b)) < share
    return fn


def _filter_into_emitter(fn, n_dest, routing, selection, traced=False):
    """Filter -> keyed StandardEmitter by hand, the emitter called straight
    from the Filter's put; ``selection`` is what the wiring would decide."""
    filt = _FilterNode(fn, "f", False, True)
    em = StandardEmitter(n_dest, routing, name="em")
    taps = [_Tap() for _ in range(n_dest)]
    em._outputs = [(t, 0) for t in taps]
    crossed = _Tap()

    class _Edge:
        def put(self, src, item):
            crossed.got.append(item)
            em.svc(item)

    filt._outputs = [(_Edge(), 0)]
    filt.emit_selection = selection
    if traced:
        filt.stats, em.stats = NodeStats("f"), NodeStats("em")
    return filt, em, taps, crossed


def _same_arrays(got, want, sources):
    assert len(got) == len(want)
    for g, w in zip(got, want):
        assert type(g) is np.ndarray and g.dtype == w.dtype
        assert g.tobytes() == w.tobytes()
        assert g.flags.c_contiguous and g.flags.owndata and g.flags.writeable
        assert not any(np.shares_memory(g, s) for s in sources)


@pytest.mark.parametrize("share", [0, 0.08, 0.92, 1])
@pytest.mark.parametrize("n_dest", [1, 2, 4])
@pytest.mark.parametrize("width", [33, 100])
def test_every_destination_receives_what_the_array_path_gives(width, n_dest,
                                                              share):
    stream = _stream(width, seed=width + n_dest)
    before = [b.tobytes() for b in stream]
    runs = {}
    for selection in (False, True):
        filt, em, taps, crossed = _filter_into_emitter(
            _predicate(share, 3), n_dest, default_routing, selection)
        for b in stream:
            filt.svc(b)
        runs[selection] = taps
        kinds = {type(x) for x in crossed.got}
        assert kinds <= ({Selection} if selection else {np.ndarray})
        assert len(crossed.got) == (0 if share == 0 else len(stream))
    assert [b.tobytes() for b in stream] == before
    for d in range(n_dest):
        _same_arrays(runs[True][d].got, runs[False][d].got, stream)
    if share == 1 and n_dest > 1:
        assert sum(len(t.got) for t in runs[True]) > len(stream)


@pytest.mark.parametrize("width", [33, 100])
def test_survivors_that_all_go_one_way_are_gathered_once(width):
    """The batch's survivors all route to worker 2 (the dropped rows would
    not): one array, the gathered survivors, and no split."""
    stream = _stream(width, seed=5)
    for b in stream:
        b["key"] = np.where(b["id"] % 3 == 0, b["key"] * 4 + 2, b["key"])
    fn = lambda b: b["id"] % 3 == 0                           # noqa: E731
    runs = {}
    for selection in (False, True):
        filt, em, taps, _ = _filter_into_emitter(fn, 4, default_routing,
                                                 selection, traced=True)
        for b in stream:
            filt.svc(b)
        runs[selection] = taps
        snap = em.stats.snapshot()
        assert snap["single_dest_batches"] == len(stream)
        assert "split_batches" not in snap
        assert snap["selection_batches"] == (len(stream) if selection else 0)
    for d in range(4):
        _same_arrays(runs[True][d].got, runs[False][d].got, stream)
        assert len(runs[True][d].got) == (len(stream) if d == 2 else 0)


@pytest.mark.parametrize("share", [0.08, 0.92])
def test_marker_rows_inside_a_batch_travel_like_any_row(share):
    stream = _stream(100, seed=9, markers=True)
    assert any(b[MARKER_FIELD].any() for b in stream)
    runs = {}
    for selection in (False, True):
        filt, _em, taps, _ = _filter_into_emitter(
            _predicate(share, 4), 4, default_routing, selection)
        for b in stream:
            filt.svc(b)
        runs[selection] = taps
    for d in range(4):
        _same_arrays(runs[True][d].got, runs[False][d].got, stream)
    assert any(g[MARKER_FIELD].any() for t in runs[True] for g in t.got)


@pytest.mark.parametrize("routing", ["reversed", "by_high_bits", "constant"])
def test_a_custom_routing_sees_the_survivors_keys(routing):
    fns = {"reversed": lambda keys, n: (n - 1) - keys % n,
           "by_high_bits": lambda keys, n: (keys // 8) % n,
           "constant": lambda keys, n: np.full(len(keys), 1)}
    seen = {False: [], True: []}
    runs = {}
    for selection in (False, True):
        def route(keys, n, _s=selection):
            seen[_s].append(np.array(keys))
            return fns[routing](keys, n)
        filt, _em, taps, _ = _filter_into_emitter(
            _predicate(0.6, 2), 3, route, selection)
        for b in _stream(33, seed=12):
            filt.svc(b)
        runs[selection] = taps
    assert len(seen[True]) == len(seen[False])
    for a, b in zip(seen[True], seen[False]):
        assert np.array_equal(a, b)
    for d in range(3):
        _same_arrays(runs[True][d].got, runs[False][d].got, ())


@pytest.mark.parametrize("n_active", [1, 2, 3])
def test_a_narrowed_emitter_routes_a_selection_over_its_active_width(
        n_active):
    """``n_active`` moves under a live graph (a rescale): at width 1 the
    selection is gathered once for worker 0, else split over the width."""
    stream = _stream(100, seed=21)
    runs = {}
    for selection in (False, True):
        filt, em, taps, _ = _filter_into_emitter(
            _predicate(0.92, 6), 4, default_routing, selection)
        filt.svc(stream[0])
        em.n_active = n_active
        for b in stream[1:]:
            filt.svc(b)
        runs[selection] = taps
    for d in range(4):
        _same_arrays(runs[True][d].got, runs[False][d].got, stream)
    assert all(len(runs[True][d].got) == 1 for d in range(n_active, 4))


def test_a_selection_is_its_rows_and_reads_only_its_base():
    b = _stream(100, seed=1, n_batches=1)[0]
    before = b.tobytes()
    mask = b["id"] % 4 != 1
    sel = Selection(b, np.flatnonzero(mask))
    assert len(sel) == int(mask.sum())
    out = sel.materialize()
    _same_arrays([out], [select_rows(b, mask)], [b])
    out["key"] += 1
    assert b.tobytes() == before and sel.base is b


# ---------------------------------------------------------------- the wiring

BIDS = Schema(event_type=np.int8, auction=np.int64)


def _events(seed, n=6000, span=5000):
    """Bids (event_type 2) on a growing key space among other events."""
    rng = np.random.default_rng(seed)
    ts = np.sort(rng.integers(0, span, n))
    auction = 1000 + ts // 7 + rng.integers(0, 30, n)
    auction = np.where(rng.random(n) < 0.5, auction // 100 * 100, auction)
    kind = np.where(rng.random(n) < 0.92, 2, rng.integers(0, 2, n))
    return batch_from_columns(BIDS, key=auction, id=np.arange(n), ts=ts,
                              event_type=kind, auction=auction)


def _is_bid(b):
    return b["event_type"] == 2


def _source(rows, chunk):
    def fn(shipper):
        for lo in range(0, len(rows), chunk):
            shipper.push_batch(rows[lo:lo + chunk].copy())
    return Source(fn, BIDS, name="src")


def _filters(df):
    """Every Filter node of a built graph, fused or not."""
    def walk(node):
        if isinstance(node, Comb):
            for s in node.stages:
                yield from walk(s)
        elif isinstance(node, _FilterNode):
            yield node
    return [f for node in df.nodes for f in walk(node)]


def _logs(trace_dir):
    return [json.loads(p.read_text()) for p in sorted(trace_dir.glob("*.log"))]


def _count_sum():
    return MultiReducer(Reducer("count", out_field="count"),
                        Reducer("sum", "auction", "total"))


def _per_key(batches):
    out = {}
    for b in batches:
        for r in b[~b[MARKER_FIELD]]:
            out.setdefault(int(r["key"]), []).append(r.tobytes())
    return out


CONSUMERS = {
    # the Filter's successor is MultiPipe's renumbering merge (CB windows)
    "ordering_merge": lambda: WinFarm(Reducer("sum", "auction", "total"), 8,
                                      4, WinType.CB, pardegree=2, name="wf"),
    "map": lambda: Map(lambda b: None, vectorized=True, name="m"),
    "win_mapreduce": lambda: WinMapReduce(
        Reducer("sum", "auction", "total"), Reducer("sum", "total", "total"),
        100, 100, WinType.TB, map_degree=2, name="wmr"),
    "round_robin_farm": lambda: Map(lambda b: None, vectorized=True,
                                    parallelism=3, name="rr"),
}


def _run_pipe(consumer, tmp_path, chained, **pipe_kw):
    rows = _events(3)
    got = []
    pipe = MultiPipe("w", trace_dir=str(tmp_path), **pipe_kw)
    pipe.add_source(_source(rows, 700))
    filt = Filter(_is_bid, vectorized=True, name="bids")
    (pipe.chain if chained else pipe.add)(filt)
    pipe.add(consumer)
    pipe.add_sink(Sink(lambda r: got.append(r.copy()) if r is not None
                       else None, vectorized=True))
    pipe.run_and_wait_end(timeout=120)
    return pipe, rows, got


@pytest.mark.parametrize("chained", [True, False])
@pytest.mark.parametrize("consumer", list(CONSUMERS))
def test_any_other_consumer_keeps_the_filter_on_arrays(consumer, chained,
                                                       tmp_path):
    pipe, rows, got = _run_pipe(CONSUMERS[consumer](), tmp_path, chained)
    filters = _filters(pipe._df)
    assert len(filters) == 1 and not filters[0].emit_selection
    logs = _logs(tmp_path)
    assert sum(v.get("filter_selections", 0) for v in logs) == 0
    assert sum(v.get("selection_batches", 0) for v in logs) == 0
    assert sum(v.get("filter_rows_out", 0) for v in logs) == \
        int(_is_bid(rows).sum())
    assert sum(len(g) for g in got) > 0


def _keyed_pipe(tmp_path, **pipe_kw):
    return _run_pipe(KeyFarm(_count_sum(), 500, 250, WinType.TB, pardegree=3,
                             name="kf"), tmp_path, True, **pipe_kw)


def test_recovery_on_keeps_every_edge_on_arrays(tmp_path):
    from windflow_tpu.recovery.policy import RecoveryPolicy
    plain, _rows, want = _keyed_pipe(tmp_path / "plain")
    assert _filters(plain._df)[0].emit_selection
    pipe, _rows, got = _keyed_pipe(
        tmp_path / "eo", recovery=RecoveryPolicy(epoch_period=0.05))
    assert not _filters(pipe._df)[0].emit_selection
    logs = _logs(tmp_path / "eo")
    assert all(v.get("filter_selections", 0) == 0 for v in logs)
    assert all(v.get("selection_batches", 0) == 0 for v in logs)
    assert _per_key(got) == _per_key(want)


@pytest.mark.parametrize("policy", ["block_deadline", "shed_newest",
                                    "error_budget"])
def test_an_overload_policy_keeps_every_edge_on_arrays(policy, tmp_path):
    from windflow_tpu.runtime.overload import OverloadPolicy
    pol = {"block_deadline": OverloadPolicy(put_deadline=30.0),
           "shed_newest": OverloadPolicy(shed="shed_newest"),
           "error_budget": OverloadPolicy(error_budget=2)}[policy]
    pipe, rows, got = _keyed_pipe(tmp_path, overload=pol)
    assert not _filters(pipe._df)[0].emit_selection
    logs = _logs(tmp_path)
    assert all(v.get("filter_selections", 0) == 0 for v in logs)
    assert all(v.get("selection_batches", 0) == 0 for v in logs)
    if policy != "shed_newest":
        plain, _r, want = _keyed_pipe(tmp_path / "plain")
        assert _per_key(got) == _per_key(want)


def test_a_second_output_edge_keeps_the_filter_on_arrays():
    """A Filter that broadcasts to two keyed emitters: two output edges,
    so each consumer gets the array it may keep."""
    rows = _events(4)
    df = Dataflow("two")
    src = _source(rows, 900).replicas()[0]
    filt = Filter(_is_bid, vectorized=True).replicas()[0]
    df.add(src)
    df.add(filt)
    df.connect(src, filt)
    taps = []
    for i in range(2):
        em = StandardEmitter(2, default_routing, name=f"em{i}")
        df.add(em)
        df.connect(filt, em)
        for d in range(2):
            got = []
            snk = Sink(lambda r, g=got: g.append(r) if r is not None
                       else None, vectorized=True,
                       name=f"s{i}{d}").replicas()[0]
            df.add(snk)
            df.connect(em, snk)
            taps.append(got)
    df.run_and_wait_end()
    assert not filt.emit_selection
    bids = rows[_is_bid(rows)]
    for i in range(2):
        for d in range(2):
            rows_d = np.concatenate(taps[2 * i + d])
            assert rows_d.tobytes() == bids[bids["key"] % 2 == d].tobytes()


def test_two_filters_into_one_emitter_both_hand_on(tmp_path):
    """Two Filters, each with its one edge into the same keyed emitter
    (a multi-in inbox): both hand selections on, and the emitter takes
    them as they come."""
    rows = _events(7)
    df = Dataflow("par", trace_dir=str(tmp_path))
    em = StandardEmitter(2, default_routing, name="em")
    df.add(em)
    filters = []
    for i in range(2):
        src = _source(rows[i::2], 500).replicas()[0]
        filt = Filter(_is_bid, vectorized=True,
                      name=f"bids{i}").replicas()[0]
        df.add(src)
        df.add(filt)
        df.connect(src, filt)
        df.connect(filt, em)
        filters.append(filt)
    taps = []
    for d in range(2):
        got = []
        snk = Sink(lambda r, g=got: g.append(r) if r is not None else None,
                   vectorized=True, name=f"s{d}").replicas()[0]
        df.add(snk)
        df.connect(em, snk)
        taps.append(got)
    df.run_and_wait_end()
    assert all(f.emit_selection for f in filters)
    bids = rows[_is_bid(rows)]
    for d in range(2):
        out = np.concatenate(taps[d])
        assert np.array_equal(np.sort(out["id"]),
                              bids["id"][bids["key"] % 2 == d])
    logs = _logs(tmp_path)
    log = [v for v in logs if v["node"].endswith("em")][0]
    assert log["selection_batches"] == log["rcv_batches"] > 0
    assert log["selection_rows"] == log["rcv_tuples"] == len(bids)
    assert sum(v.get("filter_selections", 0) for v in logs) == \
        log["rcv_batches"]


@pytest.mark.parametrize("consumer", ["keyed_map", "key_farm"])
def test_a_parallel_filter_meets_the_ordering_merge_first(consumer,
                                                          tmp_path):
    """Three Filter replicas in front of a keyed stage: MultiPipe merges
    their channels by time first, so the Filters' consumer is the merge."""
    stage = {"keyed_map": lambda: Map(lambda b: None, vectorized=True,
                                      parallelism=2, keyed=True, name="km"),
             "key_farm": lambda: KeyFarm(_count_sum(), 500, 250, WinType.TB,
                                         pardegree=2, name="kf")}[consumer]()
    rows = _events(7)
    got = []
    pipe = (MultiPipe("par", trace_dir=str(tmp_path))
            .add_source(_source(rows, 500))
            .add(Filter(_is_bid, vectorized=True, parallelism=3, name="bids"))
            .add(stage)
            .add_sink(Sink(lambda r: got.append(r.copy()) if r is not None
                           else None, vectorized=True)))
    pipe.run_and_wait_end(timeout=120)
    filters = _filters(pipe._df)
    assert len(filters) == 3 and not any(f.emit_selection for f in filters)
    logs = _logs(tmp_path)
    assert sum(v.get("filter_selections", 0) for v in logs) == 0
    assert sum(v.get("selection_batches", 0) for v in logs) == 0
    assert sum(len(g) for g in got) > 0


# ------------------------------------ the hot-items pipeline, Filter in front

def _hot_items(rows, chunk, pardegree, trace_dir=None):
    per_auction = MultiReducer(Reducer("count", out_field="count"),
                               Reducer("max", "ts", "lastUpdate"))
    hottest = MultiReducer(
        ArgReducer("max", "num", id_field="auction", id_out="auction",
                   value_range=(0, 1 << 20)),
        Reducer("sum", "bids", "bids", value_range=(0, 1 << 20)),
        Reducer("max", "lastUpdate", "lastUpdate", value_range=(0, 1 << 30)))
    got = []
    pipe = (MultiPipe("q5", trace_dir=trace_dir)
            .add_source(_source(rows, chunk))
            .chain(Filter(_is_bid, vectorized=True, name="bids"))
            .add(KeyFarmTPU(per_auction, 1000, 500, WinType.TB,
                            pardegree=pardegree, fire_on="stream",
                            name="count"))
            .add(Map(_to_counts, vectorized=True, output_schema=COUNTS,
                     name="rekey"))
            .add(WinSeqTPU(hottest, 500, 500, WinType.TB, batch_len=1,
                           flush_rows=4096, name="top"))
            .add_sink(Sink(lambda r: got.append(r.copy()) if r is not None
                           else None, vectorized=True)))
    pipe.run_and_wait_end(timeout=120)
    out = np.concatenate(got)
    out = out[out["bids"] > 0]
    return pipe, {int(r["id"]) - 1: (int(r["auction"]), int(r["num"]),
                                     int(r["bids"]), int(r["lastUpdate"]))
                  for r in out}


@pytest.mark.parametrize("chunk", [257, 3000])
@pytest.mark.parametrize("pardegree", [2, 4])
def test_hot_items_with_its_filter_against_the_plain_reference(
        pardegree, chunk, tmp_path):
    from oracle import hot_items_windows
    rows = _events(11, n=12000, span=9000)
    pipe, got = _hot_items(rows, chunk, pardegree, trace_dir=str(tmp_path))
    bids = rows[_is_bid(rows)]
    assert got == hot_items_windows(bids, 1000, 500)
    assert _filters(pipe._df)[0].emit_selection
    logs = {v["node"].split("_", 2)[2]: v for v in _logs(tmp_path)}
    src, em = logs["src.0+bids.0"], logs["count.emitter"]
    emitting = -(-len(rows) // chunk)         # every chunk holds a bid
    assert src["filter_selections"] == emitting
    assert src["filter_rows_out"] == len(bids)
    assert em["selection_batches"] == em["rcv_batches"] == emitting
    assert em["selection_rows"] == em["rcv_tuples"] == len(bids)
    workers = [logs[f"count.{i}"] for i in range(pardegree)]
    assert sum(w["rcv_tuples"] for w in workers) == len(bids)
    assert "filter_selections" not in logs["rekey.0"]


def test_hot_items_is_the_same_under_recovery_on_arrays(tmp_path):
    from oracle import hot_items_windows
    from windflow_tpu.recovery.policy import RecoveryPolicy
    rows = _events(13)
    bids = rows[_is_bid(rows)]
    got = []
    pipe = (MultiPipe("eo", trace_dir=str(tmp_path),
                      recovery=RecoveryPolicy(epoch_period=0.05))
            .add_source(_source(rows, 400))
            .chain(Filter(_is_bid, vectorized=True, name="bids"))
            .add(KeyFarm(MultiReducer(Reducer("count", out_field="count"),
                                          Reducer("max", "ts", "lastUpdate")),
                             1000, 500, WinType.TB, pardegree=4,
                             fire_on="stream", name="count"))
            .add_sink(Sink(lambda r: got.append(r.copy()) if r is not None
                           else None, vectorized=True)))
    pipe.run_and_wait_end(timeout=120)
    assert not _filters(pipe._df)[0].emit_selection
    out = np.concatenate(got)
    out = out[~out[MARKER_FIELD]]
    want = {}
    for wid, (_a, _n, total, _t) in hot_items_windows(bids, 1000,
                                                      500).items():
        want[wid] = total
    have = {}
    for r in out:
        have[int(r["id"])] = have.get(int(r["id"]), 0) + int(r["count"])
    assert have == want
    assert all(v.get("filter_selections", 0) == 0 and
               v.get("selection_batches", 0) == 0 for v in _logs(tmp_path))


# ------------------------------------------------- what the row counts read

def test_hop_records_and_rcv_tuples_are_equal_on_both_paths(tmp_path):
    """``rcv_tuples`` of every node and the ``rows`` of every hop record
    read ``len()`` of what crossed: the survivors, selection or array."""
    from obs_schema import validate_file, validate_span
    rows = _events(17)
    from windflow_tpu.runtime.overload import OverloadPolicy
    seen = {}
    # the array path of the same graph: a put deadline nothing reaches is
    # an overload policy, under which the wiring leaves the flag off
    for name, kw in (("selection", {}),
                     ("array", {"overload": OverloadPolicy(put_deadline=60.0)})):
        d = tmp_path / name
        pipe = (MultiPipe("hops", trace_dir=str(d), trace=1.0, metrics=True,
                          **kw)
                .add_source(_source(rows, 600))
                .chain(Filter(_is_bid, vectorized=True, name="bids"))
                .add(Map(lambda b: None, vectorized=True, parallelism=3,
                         keyed=True, name="km"))
                .add_sink(Sink(lambda r: None, vectorized=True)))
        pipe.run_and_wait_end(timeout=120)
        assert _filters(pipe._df)[0].emit_selection == (name == "selection")
        assert validate_file(str(d / "trace.jsonl"), validate_span) > 0
        hops = {}
        for line in open(d / "trace.jsonl"):
            rec = json.loads(line)
            if rec["kind"] == "hop":
                node = rec["node"].split("_", 2)[2]
                hops.setdefault(node, []).append(rec["rows"])
        tuples = {v["node"].split("_", 2)[2]: (v["rcv_batches"],
                                               v["rcv_tuples"])
                  for v in _logs(d)}
        seen[name] = ({k: sorted(v) for k, v in hops.items()}, tuples)
    assert seen["selection"] == seen["array"]
    hops, tuples = seen["selection"]
    n_bids = int(_is_bid(rows).sum())
    assert tuples["km.emitter"] == (10, n_bids)
    assert sum(hops["km.emitter"]) == n_bids
