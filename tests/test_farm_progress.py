"""A farm emitter's progress rows (patterns/win_farm.py:_send_progress), end
to end: a worker of a time-based farm closes a window when the key has
passed its end, not when the worker's own next window starts.  The graphs
that are compared with ``Win_Seq`` stay equal row for row and their workers
fire on the markers; a pane reaches the sink with no later row of its
worker; every core that can sit under such a farm takes a marker in
mid-stream and gives the results it gives without; and ``filter`` makes as
many passes over a batch as it did (the emitter's rule itself:
tests/test_farms.py)."""

import threading
import warnings

import numpy as np
import pytest

from windflow_tpu import native
from windflow_tpu.api import MultiPipe
from windflow_tpu.core.tuples import (MARKER_FIELD, Schema,
                                      batch_from_columns)
from windflow_tpu.core.vecinc import make_vec_core
from windflow_tpu.core.windows import WindowSpec, WinType
from windflow_tpu.core.winseq import WinSeqCore
from windflow_tpu.ops.functions import Reducer
from windflow_tpu.patterns.basic import Sink, Source
from windflow_tpu.patterns.pane_farm import PaneFarm
from windflow_tpu.patterns.win_farm import WinFarm
from windflow_tpu.patterns.win_seq import WinSeq, window_cores
from windflow_tpu.patterns.win_seq_tpu import (DeviceWinSeqCore,
                                               JaxWindowFunction,
                                               PaneFarmTPU,
                                               ResidentWinSeqCore,
                                               WinFarmTPU)
from windflow_tpu.runtime.emitters import KeyedStreamState

from test_farms import cb_stream_batches, run_windowed, tb_stream_batches
from test_pane_wmr import iv

SCHEMA = Schema(value=np.int64)


def _last_pos(batches) -> dict:
    rows = np.concatenate(batches)
    return {int(k): int(rows["ts"][rows["key"] == k].max())
            for k in np.unique(rows["key"])}


def _by_progress(df, spec=None) -> list:
    """``windows_fired_by_progress`` of the cores of ``df`` that count it:
    the workers of a farm whose emitter sends progress rows (of those over
    ``spec``'s window length, where given)."""
    counts = [getattr(c, "windows_fired_by_progress", None)
              for c in window_cores(df)
              if spec is None or c.spec.win_len == spec]
    return [c for c in counts if c is not None]


def _row_by_row(keys, n):
    """A stream a row a batch whose ts moves by 8 at most: a batch passes
    one end of a window of 10 or more at most, and its row lies in the NEXT
    window, another worker's — so every window that ends before its key's
    last row is closed by a progress row and none by a row."""
    return tb_stream_batches(keys, n, chunk=1)


# ------------------------------------------------- (b) the graphs, row for row

@pytest.mark.parametrize("degree", [2, 3])
@pytest.mark.parametrize("win,slide", [(30, 30), (15, 40)])
def test_a_tb_farm_equals_win_seq_and_its_workers_close_on_progress_rows(
        win, slide, degree):
    """Tumbling and hopping: every window that ended before its key's last
    row was fired by a worker on a marker — the share is 1 up to the flush,
    which brings the windows the stream's end left open."""
    batches = tb_stream_batches(2, 150, chunk=11)
    ref = run_windowed(WinSeq(Reducer("sum"), win, slide, WinType.TB),
                       batches)
    graph = []
    got = run_windowed(WinFarm(Reducer("sum"), win, slide, WinType.TB,
                               pardegree=degree), batches, graph)
    assert got == ref
    last = _last_pos(batches)
    ended = sum(1 for k, rs in got.items() for r in rs
                if r[0] * slide + win <= last[k])
    counts = _by_progress(graph[0])
    assert len(counts) == degree and min(counts) > 0
    assert sum(counts) == ended > 20


def test_a_sliding_farm_multicasts_and_fires_nothing_on_a_marker():
    batches = tb_stream_batches(2, 150, chunk=11)
    graph = []
    got = run_windowed(WinFarm(Reducer("sum"), 40, 15, WinType.TB,
                               pardegree=2), batches, graph)
    assert got == run_windowed(
        WinSeq(Reducer("sum"), 40, 15, WinType.TB), batches)
    # two windows over every row, two workers: nobody is ever left out, the
    # emitter sends no row and its workers keep no count, though each met
    # the end-of-stream replay's marker
    assert _by_progress(graph[0]) == []


@pytest.mark.parametrize("win,slide,wt,degree,counts", [
    (30, 30, WinType.TB, 2, True),      # tumbling: one window over a row
    (40, 15, WinType.TB, 3, True),      # sliding, 2-3 over a row, 3 workers
    (40, 15, WinType.TB, 2, False),     # sliding, 2 workers: multicast
    (8, 8, WinType.CB, 2, False)])      # count-based: never
def test_which_workers_count_is_a_matter_of_the_graph_not_of_the_run(
        win, slide, wt, degree, counts):
    """A farm's workers report ``windows_fired_by_progress`` where its
    emitter can send a progress row, from the moment they are built and
    whatever the stream brings; every other worker reports none, not even
    after the end-of-stream replay has closed its windows on a marker."""
    farm = WinFarm(Reducer("sum"), win, slide, wt, pardegree=degree)
    built = [getattr(r.core, "windows_fired_by_progress", None)
             for r in farm.replicas()]
    assert built == ([0] * degree if counts else [None] * degree)
    assert farm.emitter()._progress is counts
    batches = (tb_stream_batches(2, 150, chunk=11) if wt is WinType.TB
               else cb_stream_batches(2, 150, chunk=11))
    graph = []
    run_windowed(WinFarm(Reducer("sum"), win, slide, wt, pardegree=degree),
                 batches, graph)
    assert len(_by_progress(graph[0])) == (degree if counts else 0)


@pytest.mark.parametrize("plq,wlq", [(2, 1), (3, 2)])
def test_a_tb_pane_farm_equals_win_seq_and_its_pane_workers_close_on_progress(
        plq, wlq):
    win, slide, pane = 40, 30, 10
    batches = _row_by_row(2, 150)
    graph = []
    got = run_windowed(
        PaneFarm(Reducer("sum"), Reducer("sum"), win, slide, WinType.TB,
                 plq_degree=plq, wlq_degree=wlq), batches, graph)
    assert iv(got) == iv(run_windowed(
        WinSeq(Reducer("sum"), win, slide, WinType.TB), batches))
    # a key's panes that ended before its last row, empty ones too
    ended = sum(p // pane for p in _last_pos(batches).values())
    counts = _by_progress(graph[0], spec=pane)
    assert len(counts) == plq and min(counts) > 0
    assert sum(counts) == ended
    # the window stage is count-based over the panes' ids: at any degree
    # its workers get no progress row and join no share of them
    assert len(_by_progress(graph[0])) == plq


def _jax_sum():
    import jax.numpy as jnp
    return JaxWindowFunction(
        lambda keys, gwids, cols, mask: jnp.sum(
            jnp.where(mask, cols["value"], 0), axis=1),
        fields=("value",), result_fields={"value": np.int64})


def test_a_device_pane_stage_closes_on_progress_rows_too():
    """``PaneFarmTPU`` with a user's pane function on the Python resident
    core: the benchmark's ``spatial_pf`` shape."""
    win, slide, pane = 40, 20, 20
    batches = _row_by_row(1, 200)
    graph = []
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        got = run_windowed(
            PaneFarmTPU(_jax_sum(), Reducer("sum"), win, slide,
                        WinType.TB, plq_degree=2, wlq_degree=1,
                        plq_on_device=True, wlq_on_device=False, batch_len=1,
                        use_resident=True), batches, graph)
    assert iv(got) == iv(run_windowed(
        WinSeq(Reducer("sum"), win, slide, WinType.TB), batches))
    cores = [c for c in window_cores(graph[0]) if c.spec.win_len == pane]
    assert [type(c).__name__ for c in cores] == ["ResidentWinSeqCore"] * 2
    assert sum(c.windows_fired_by_progress for c in cores) \
        == _last_pos(batches)[0] // pane > 20


def test_a_device_farm_closes_on_progress_rows_too():
    batches = tb_stream_batches(2, 150, chunk=11)
    graph = []
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        got = run_windowed(WinFarmTPU(Reducer("sum"), 30, 30, WinType.TB,
                                      pardegree=2), batches, graph)
    assert got == run_windowed(
        WinSeq(Reducer("sum"), 30, 30, WinType.TB), batches)
    # (the native core fires on the markers and keeps no count of it)
    assert [type(c).__name__ for c in window_cores(graph[0])] \
        == ["NativeResidentCore"] * 2


def test_the_workers_logs_say_how_many_windows_a_progress_row_closed(
        tmp_path):
    import json
    import os
    batches = _row_by_row(1, 200)
    run_windowed(WinFarm(Reducer("sum"), 30, 30, WinType.TB, pardegree=2),
                 batches, trace_dir=str(tmp_path))
    logs = {}
    for fn in os.listdir(tmp_path):
        if fn.endswith(".log"):
            with open(tmp_path / fn) as f:
                node = json.load(f)
            logs[node["node"]] = node
    emitter = next(n for name, n in logs.items() if name.endswith("emitter"))
    workers = [n for n in logs.values() if "windows_fired_by_progress" in n]
    assert len(workers) == 2
    fired = sum(n["windows_fired"] for n in workers)
    by_progress = sum(n["windows_fired_by_progress"] for n in workers)
    # every pane but the one the stream's end left open; a row sent for
    # each (and one more where the last pane's end was passed as well)
    assert by_progress == fired - 1 > 20
    assert by_progress <= emitter["progress_sent"] <= by_progress + 1
    assert sum(n["progress_seen"] for n in workers) \
        == emitter["progress_sent"] + 2        # + the end's replay, each


# ---------------------------------------------------------------- (c) liveness

@pytest.mark.parametrize("farm", ["win_farm", "pane_farm", "pane_farm_tpu"])
def test_a_pane_reaches_the_sink_with_no_later_row_of_its_worker(farm):
    """Tumbling windows of 10 over a farm of two: window 0, ts [0, 10), is
    worker 0's, whose next window starts at ts 20.  The source sends ts
    0..10 — the first row of worker 1's window — and then NOTHING until the
    sink holds window 0.  Under ``wf_nodes.hpp``'s routing alone worker 0
    sees ts 10 never and closes window 0 at the end of the stream."""
    at_sink, waited, got = threading.Event(), [], []

    def src(shipper):
        ts = np.arange(11)
        shipper.push_batch(batch_from_columns(
            SCHEMA, key=np.zeros(11), id=ts, ts=ts, value=ts))
        waited.append(at_sink.wait(timeout=60))    # a bound, not a pace

    def snk(rows):
        if rows is not None and len(rows):
            got.extend(rows["id"].tolist())
            if 0 in rows["id"]:
                at_sink.set()

    sm = Reducer("sum")
    if farm == "win_farm":
        agg = WinFarm(sm, 10, 10, WinType.TB, pardegree=2)
    elif farm == "pane_farm":
        # panes of 10, windows of 20 every 10; the window stage needs
        # window 0's last pane, pane 1: the source below sends up to ts 20
        agg = PaneFarm(sm, sm, 20, 10, WinType.TB, plq_degree=2,
                       wlq_degree=1)
    else:
        agg = PaneFarmTPU(_jax_sum(), sm, 20, 10, WinType.TB, plq_degree=2,
                          wlq_degree=1, plq_on_device=True,
                          wlq_on_device=False, batch_len=1,
                          use_resident=True)
    if farm != "win_farm":
        def src(shipper):      # noqa: F811  (pane 1 closed by ts 20)
            ts = np.arange(21)
            shipper.push_batch(batch_from_columns(
                SCHEMA, key=np.zeros(21), id=ts, ts=ts, value=ts))
            waited.append(at_sink.wait(timeout=60))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        (MultiPipe("farm_live")
         .add_source(Source(src, SCHEMA, fresh=True)).add(agg)
         .chain_sink(Sink(snk, vectorized=True))).run_and_wait_end(
             timeout=120)
    assert waited == [True]
    assert got[0] == 0 and got == sorted(got)


# ------------------------------------------------- the worker's turn

class _Turn:
    """An event that writes what is done with it into ``log``."""

    def __init__(self, log, d):
        self.log, self.d = log, d

    def clear(self):
        self.log.append(("clear", self.d))

    def wait(self, timeout=None):
        self.log.append(("wait", self.d))
        return True


class _LogTap:
    def __init__(self, log, d):
        self.log, self.d = log, d

    def put(self, src, batch):
        self.log.append(("marker" if batch[MARKER_FIELD].all() else "rows",
                         self.d))


@pytest.mark.parametrize("traced", [False, True])
def test_an_emitter_with_turns_waits_for_each_worker_it_sent_a_progress_row(
        traced):
    """Where the farm gives it an event a worker: cleared, then the row,
    then the wait, for exactly the workers that got a row; a batch that
    passes no end waits for nobody; with tracing on the wait is blocked
    time."""
    from windflow_tpu.patterns.win_farm import WFEmitterNode
    from windflow_tpu.utils.tracing import NodeStats
    log = []
    em = WFEmitterNode(WindowSpec(30, 30, WinType.TB), 2, name="em",
                       turns=[_Turn(log, 0), _Turn(log, 1)])
    em._outputs = [(_LogTap(log, d), 0) for d in range(2)]
    if traced:
        em.stats = NodeStats("em")
    waits = 0
    for b in tb_stream_batches(1, 120, chunk=7, seed=3):
        del log[:]
        em.svc(b)
        told = [d for what, d in log if what == "marker"]
        assert len(told) <= 1
        for d in told:
            assert [x for x in log if x[0] != "rows"] == [
                ("clear", d), ("marker", d), ("wait", d)]
            waits += 1
        if not told:
            assert all(what == "rows" for what, _ in log)
    assert waits > 10
    if traced:
        assert em.stats.blocked_ns > 0


def test_a_turn_nobody_takes_holds_the_emitter_for_its_bound_alone(
        monkeypatch):
    from windflow_tpu.patterns import win_farm
    monkeypatch.setattr(win_farm, "_TURN_WAIT_S", 0.01)
    em = win_farm.WFEmitterNode(
        WindowSpec(30, 30, WinType.TB), 2, name="em",
        turns=[threading.Event(), threading.Event()])
    em._outputs = [(_LogTap([], d), 0) for d in range(2)]
    for b in tb_stream_batches(1, 60, chunk=7, seed=3):
        em.svc(b)                       # (returns: nobody ever sets one)
    assert not any(t.is_set() for t in em._turns)


def test_a_worker_sets_its_turn_when_it_has_served_a_marker_and_only_then():
    farm = WinFarmTPU(Reducer("sum"), 30, 30, WinType.TB, pardegree=2)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        node = farm.replicas()[0]
    assert node.turn is farm._turns[0] and not node.turn.is_set()
    rows = tb_stream_batches(1, 20, chunk=20, seed=1)[0]
    node.emit = lambda out: None
    node.svc(rows)
    assert not node.turn.is_set()
    mark = rows[-1:].copy()
    mark[MARKER_FIELD] = True
    node.svc(mark)
    assert node.turn.is_set()


@pytest.mark.parametrize("make,gives", [
    (lambda: WinFarmTPU(Reducer("sum"), 30, 30, WinType.TB, pardegree=2),
     True),
    (lambda: WinFarmTPU(Reducer("sum"), 30, 30, WinType.TB, pardegree=2,
                        n_emitters=2), False),      # behind a merge
    (lambda: WinFarmTPU(Reducer("sum"), 40, 15, WinType.TB, pardegree=2),
     False),                                        # multicast
    (lambda: WinFarmTPU(Reducer("sum"), 8, 8, WinType.CB, pardegree=2),
     False),                                        # count-based
    (lambda: WinFarm(Reducer("sum"), 30, 30, WinType.TB, pardegree=2),
     False)])                                       # host workers
def test_only_a_farm_of_device_workers_behind_one_emitter_gives_turns(
        make, gives):
    """A worker's turn is for a launch's host part: a farm of host cores
    gives none (its window function runs beside the intake), nor does a
    farm whose emitter sends no progress row or is one of several."""
    farm = make()
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        nodes = farm.replicas()
    em = farm.emitter()
    if gives:
        assert [n.turn for n in nodes] == em._turns == farm._turns
        assert len({id(t) for t in em._turns}) == 2
    else:
        assert em._turns is None
        assert all(getattr(n, "turn", None) is None for n in nodes)


# ------------------------------------------ (d) a marker in mid-stream, a core

def _with_markers(batches):
    """``batches`` with, ahead of every batch but the first, the first row
    of each of its keys as a marker of its own batch: a promise the stream
    keeps (every later row of the key is at or past it), as the farm
    emitter's is."""
    out = [batches[0]]
    for b in batches[1:]:
        _k, first = np.unique(b["key"], return_index=True)
        marks = b[np.sort(first)].copy()
        marks[MARKER_FIELD] = True
        out += [marks, b]
    return out


def _drive(core, batches):
    outs = [core.process(b) for b in batches] + [core.flush()]
    rows = np.concatenate([o for o in outs if len(o)])
    rows = rows[~rows[MARKER_FIELD]] if MARKER_FIELD in rows.dtype.names \
        else rows
    return np.sort(rows, order=["key", "id"])


def _native(spec):
    if not native.available():
        pytest.skip("native library unavailable")
    from windflow_tpu.patterns.native_core import NativeResidentCore
    return NativeResidentCore(spec, Reducer("sum"), batch_len=4)


CORES = {
    "winseq_nic": lambda spec: WinSeqCore(spec, Reducer("sum")),
    "winseq_inc": lambda spec: WinSeqCore(
        spec, Reducer("sum")).use_incremental(),
    "vectorised": lambda spec: make_vec_core(spec, Reducer("sum")),
    "restage": lambda spec: DeviceWinSeqCore(spec, Reducer("sum"),
                                             batch_len=4),
    "resident_py": lambda spec: ResidentWinSeqCore(spec, Reducer("sum"),
                                                   batch_len=4),
    "native": _native,
}
GEOMETRY = {"tumbling": (30, 30), "sliding": (40, 15), "hopping": (15, 40)}


@pytest.mark.parametrize("core,kind", [
    (c, k) for c in sorted(CORES) for k in sorted(GEOMETRY)
    # (hopping windows stay on the general core)
    if (c, k) != ("vectorised", "hopping")])
def test_a_core_fed_a_marker_in_mid_stream_gives_the_same_results(core, kind):
    """Every core a time-based farm can build as a worker: the windows a
    marker closes early are the windows the key's next row would have
    closed, with the same rows, ids and result ts."""
    spec = WindowSpec(*GEOMETRY[kind], WinType.TB)
    batches = tb_stream_batches(3, 120, chunk=13, seed=5)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        plain = _drive(CORES[core](spec), batches)
        marked_core = CORES[core](spec)
        marked = _drive(marked_core, _with_markers(batches))
    assert len(plain) > 30
    assert plain.dtype == marked.dtype
    assert plain.tobytes() == marked.tobytes()
    by_progress = getattr(marked_core, "windows_fired_by_progress", None)
    if by_progress is not None:     # the cores on WinSeqCore._process_key
        assert by_progress > 0


# ----------------------------------------------- (e) what a batch costs filter

class _Counting:
    """``lib`` with every call counted by name."""

    def __init__(self, lib):
        self._lib, self.calls = lib, {}

    def __getattr__(self, name):
        fn = getattr(self._lib, name)

        def counted(*args):
            self.calls[name] = self.calls.get(name, 0) + 1
            return fn(*args)
        return counted


@pytest.mark.parametrize("keys", [1, 5])
def test_filter_makes_one_native_pass_a_batch_and_hands_out_one_value_a_key(
        keys):
    """The per-row path cannot grow unseen: an in-order batch is one slot
    lookup and one ordered scan, as before the emitter asked for a key's
    position before the batch, and what is handed out is as long as the
    batch has distinct keys."""
    if not native.available():
        pytest.skip("native library unavailable")
    state = KeyedStreamState("ts")
    assert state._km is not None
    state._lib = lib = _Counting(state._lib)
    before = {}
    batches = tb_stream_batches(keys, 100, chunk=64)
    for i, b in enumerate(batches):
        assert state.filter(b) is b
        assert lib.calls == {"wf_keymap_lookup": i + 1,
                             "wf_keyscan_ordered": i + 1}
        ks = np.unique(b["key"])
        assert len(state.key_slots) == len(state.key_prev) == len(ks)
        rows = state.last_rows(state.key_slots)
        for k, prev, now in zip(rows["key"].tolist(),
                                state.key_prev.tolist(),
                                state.key_now.tolist()):
            assert prev == before.get(k, -2 ** 62)
            assert now == int(b["ts"][b["key"] == k].max())
            before[k] = now
    del state._lib       # (the keymap is freed through the real library)
    state._lib = lib._lib


@pytest.mark.parametrize("keys", [1, 5])
def test_filter_on_the_numpy_path_sorts_a_batch_once(keys, monkeypatch):
    from windflow_tpu.runtime import emitters
    monkeypatch.setattr(native, "load", lambda: None)
    state = KeyedStreamState("ts")
    assert state._km is None
    calls = {"argsort": 0, "take": 0}
    real_argsort, real_take = np.argsort, emitters.take_rows

    def argsort(*a, **kw):
        calls["argsort"] += 1
        return real_argsort(*a, **kw)

    def take(batch, idx):
        calls["take"] += 1
        return real_take(batch, idx)
    monkeypatch.setattr(emitters.np, "argsort", argsort)
    monkeypatch.setattr(emitters, "take_rows", take)
    batches = tb_stream_batches(keys, 200, chunk=64)
    assert state.filter(batches[0]) is batches[0]    # (registers the keys)
    for b in batches[1:]:
        seen = dict(calls)
        assert state.filter(b) is b
        # one stable sort by slot, one gather of a row a key: as before the
        # emitter asked for a key's position before the batch
        assert calls == {"argsort": seen["argsort"] + 1,
                         "take": seen["take"] + 1}
        assert len(state.key_slots) == len(np.unique(b["key"]))


def test_an_out_of_order_batch_hands_out_its_survivors_keys():
    state = KeyedStreamState("ts")
    b = batch_from_columns(SCHEMA, key=[0, 1, 0, 1], id=np.arange(4),
                           ts=[5, 9, 3, 12], value=np.arange(4))
    out = state.filter(b)
    assert out["ts"].tolist() == [5, 9, 12]
    rows = state.last_rows(state.key_slots)
    assert sorted(zip(rows["key"].tolist(), state.key_prev.tolist(),
                      state.key_now.tolist())) \
        == [(0, -2 ** 62, 5), (1, -2 ** 62, 12)]
    # an absorbed marker moves its key and is not among them; a row behind
    # it does not survive
    m = batch_from_columns(SCHEMA, key=[0, 1], id=[9, 9], ts=[20, 11],
                           value=[0, 0])
    m[MARKER_FIELD] = [True, False]
    assert len(state.filter(m)) == 0
    assert state.key_slots is None
    assert len(state.filter(b[:1])) == 0        # ts 5 lies behind key 0's 20
