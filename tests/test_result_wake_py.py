"""The Python resident core wakes its node too (ISSUE 44): a launch of a
``ResidentWinSeqCore`` whose result lands while the worker's node sits idle
in ``inbox.get()`` is taken and emitted then (``set_waker`` / ``collect`` /
``WinSeqNode.on_wake``, the native core's contract of ISSUE 32), instead of
waiting in the executor for the next chunk's ``process()``.

* ONE chunk that closes windows and then a silent stream: the results reach
  the sink before the stream ends — a two-worker farm around a user's
  ``JaxWindowFunction``, and a built-in ``Reducer`` on the Python core;
* oracle-equal and in order at the sink, paced and unpaced;
* ``max_delay_ms``, the native core's Python delegate and a node under
  ``recovery=`` take no waker, start no thread, and emit as they did;
* a failing fetch is raised once, on the node's thread, and loses no window;
* the watcher thread ends with the stream and with the core;
* ``result_wakes``, ``result_wake_rows`` and each ``harvest_wait`` record's
  ``handed`` say what the wake took.
"""

import gc
import json
import threading
import time
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from windflow_tpu.core.tuples import Schema, batch_from_columns
from windflow_tpu.ops.functions import MultiReducer, Reducer
from windflow_tpu.utils import profile

from test_result_wake import LONG, _Stream, handed  # noqa: E402
from test_flush_early import CB, N_KEYS  # noqa: E402
from test_ship_harvest import (assert_same, cb_chunks, oracle,  # noqa: E402
                               wait_for)
from windflow_tpu.patterns.native_core import NativeResidentCore  # noqa: E402
from windflow_tpu.patterns.win_seq import WinSeqNode  # noqa: E402
from windflow_tpu.patterns.win_seq_tpu import (  # noqa: E402
    JaxWindowFunction, ResidentWinSeqCore, WinFarmTPU, WinSeqTPU)


@pytest.fixture(autouse=True)
def _python_core_with_profile_on(monkeypatch):
    # the router gives a built-in Reducer the Python resident core
    monkeypatch.setenv("WF_NO_NATIVE_CORE", "1")
    monkeypatch.delenv("WF_PROFILE", raising=False)
    profile.enable()
    profile.reset()
    yield
    profile.auto()
    profile.reset()


FLUSH_ROWS = 1 << 13


def watchers():
    return [t for t in threading.enumerate()
            if t.name.startswith("wf-watch")]


def slow_py_device(core, idle):
    """Make every launch of the Python resident `core` what it is on the
    chip at a paced rate: not there at the poll of the ``process()`` that
    dispatched it, there once `idle()` says the caller has gone back to
    waiting — for the poll and for the watcher's wait alike."""
    ex = core.executor
    landed = []         # the launches whose result is there

    def is_ready(out):
        if not any(o is out for o in landed) and idle():
            landed.append(out)
        return any(o is out for o in landed)

    def wait_ready(out):
        t_end = time.monotonic() + LONG / 4
        while not idle() and time.monotonic() < t_end:
            time.sleep(0.0005)
        jax.block_until_ready(out)

    ex._is_ready, ex.wait_ready = is_ready, wait_ready
    return core


class _PyStream(_Stream):
    """``_Stream`` over stages whose cores are ``ResidentWinSeqCore``s."""

    def _slow(self, core, node):
        # (a graph that failed serves nothing more: nothing waits for it)
        slow_py_device(
            core, lambda: (self.done[id(node)] == self.fed[id(node)]
                           or self.df._failed.is_set()))

    def quiet(self):
        """Every node has served what it was fed, and every launch made is
        harvested — by a wake, for every node sits idle."""
        wait_for(lambda: self.fed == self.done, LONG,
                 "every node to have served its input")
        wait_for(lambda: len(handed()) == sum(c.executor.dispatches
                                              for c in self.cores),
                 LONG, "every launch to be harvested")


def _sum_fn():
    return JaxWindowFunction(
        lambda k, g, c, m: jnp.sum(jnp.where(m, c["value"], 0), axis=1),
        fields=("value",), result_fields={"value": np.int64})


def _stage(kind, **kw):
    # every key's fire is a launch, as in ``spatial_wf`` (batch_len 1);
    # no launch for rows alone (flush_rows also sizes the ring)
    kw.setdefault("batch_len", 1)
    kw.setdefault("flush_rows", FLUSH_ROWS)
    geo = (CB.win_len, CB.slide_len, CB.win_type)
    if kind == "jax_farm":
        return WinFarmTPU(_sum_fn(), *geo, pardegree=2, use_resident=True,
                          **kw)
    if kind == "reducer_farm":
        return WinFarmTPU(Reducer("sum"), *geo, pardegree=2, **kw)
    return WinSeqTPU(Reducer("sum"), *geo, **kw)


KINDS = ["jax_farm", "reducer_farm", "reducer"]


def _python_cores(s, n):
    assert len(s.cores) == n
    assert all(type(c) is ResidentWinSeqCore for c in s.cores)


# ------------------------------------- one chunk, then a silent stream

@pytest.mark.parametrize("kind", ["jax_farm", "reducer"])
def test_one_chunk_then_silence_delivers_before_the_stream_ends(kind):
    chunk, = cb_chunks(1)
    want = oracle(CB, Reducer("sum"), [chunk])
    closed = 96         # of its 128 windows the chunk itself closes these
    seen = []

    def silence(s):
        # nothing more comes; at the parent the launches' results lay in
        # the executor until the end-of-stream flush, however long this took
        try:
            wait_for(lambda: s.rows() >= closed, LONG / 2, "the results")
            seen.append((s.rows(), len(watchers())))
        except AssertionError:
            seen.append(None)

    s = _PyStream(_stage(kind), [chunk], silence, slow=True)
    n = 2 if kind == "jax_farm" else 1
    _python_cores(s, n)
    before = threading.active_count()
    got = s.run()
    # (one watcher a core while the stream runs)
    assert seen == [(closed, n)], "the result waited for the stream's end"
    assert s.at[0] < s.ended
    assert_same(got[list(want.dtype.names)], want)
    assert not watchers() and threading.active_count() <= before
    assert all(c.result_wake_rows for c in s.cores)
    assert sum(c.result_wake_rows for c in s.cores) == closed


# ----------------------------------------- oracle-equal, and in order

@pytest.mark.parametrize("paced", [False, True])
@pytest.mark.parametrize("kind", KINDS)
def test_the_python_core_is_oracle_equal_and_in_order(kind, paced):
    chunks = cb_chunks(24)
    want = oracle(CB, Reducer("sum"), chunks)
    s = _PyStream(_stage(kind), chunks,
                  between=_PyStream.quiet if paced else None, slow=paced)
    _python_cores(s, 1 if kind == "reducer" else 2)
    got = s.run()
    assert_same(got[list(want.dtype.names)], want)
    # behind the ordered collector every key's windows arrive in order
    arrived = np.concatenate(s.got)
    for k in np.unique(arrived["key"]):
        ids = arrived["id"][arrived["key"] == k]
        assert (np.diff(ids) > 0).all()
    how = handed()
    counters = profile.counters()
    assert len(how) == counters["launches"] == sum(
        c.executor.dispatches for c in s.cores)
    assert set(how) <= {"wake", "svc"}
    assert counters.get("result_wakes", 0) == how.count("wake") == sum(
        c.result_wakes for c in s.cores)
    assert counters.get("result_wake_rows", 0) == sum(
        c.result_wake_rows for c in s.cores)
    if paced:
        # every launch left with a wake, before the next chunk came; the
        # end-of-stream flush drained its own: a key's last windows each,
        # and what was left
        n_wake = how.count("wake")
        assert how == ["wake"] * n_wake + ["svc"] * (len(how) - n_wake)
        assert len(how) - n_wake <= (N_KEYS + 1) * len(s.cores)
        assert n_wake >= 24 * N_KEYS
    assert not watchers()


# ------------------------ the paths that are sent no wake, as they were

def _int32_chunks(n_chunks, seed=31):
    """A payload column the native core does not stage: it hands the
    stream to its Python delegate at the first chunk."""
    schema = Schema(value=np.int32)
    out = []
    for b in cb_chunks(n_chunks, seed=seed):
        out.append(batch_from_columns(
            schema, key=b["key"], id=b["id"], ts=b["ts"],
            value=b["value"].astype(np.int32)))
    return schema, out


@pytest.mark.parametrize("mode", ["max_delay", "recovery", "delegate"])
def test_a_core_off_the_clock_takes_no_waker_and_starts_no_thread(
        mode, monkeypatch):
    from windflow_tpu.recovery.policy import RecoveryPolicy
    chunks = cb_chunks(12, seed=7)
    fn = Reducer("sum")
    kw, pipe_kw, schema = {}, {}, None
    if mode == "max_delay":
        kw = dict(max_delay_ms=600_000.0)
    elif mode == "recovery":
        pipe_kw = dict(recovery=RecoveryPolicy())
    else:
        monkeypatch.delenv("WF_NO_NATIVE_CORE")
        fn = MultiReducer(("sum", "value", "value"), ("max", "value", "hi"),
                          dtype=np.int64)
        schema, chunks = _int32_chunks(12)
    want = oracle(CB, fn, chunks)
    seen, tokens = [], []
    stage = WinSeqTPU(fn, CB.win_len, CB.slide_len, CB.win_type,
                      batch_len=64, flush_rows=FLUSH_ROWS, **kw)
    if schema is not None:
        pipe_kw["schema"] = schema
    s = _PyStream(stage, chunks, lambda s: seen.append(len(watchers())),
                  **pipe_kw)
    node, = [n for n in s.df.nodes if isinstance(n, WinSeqNode)]
    ib = s.df._inboxes[id(node)]
    wake = ib.wake
    ib.wake = lambda: (tokens.append(1), wake())
    got = s.run()
    assert_same(got[list(want.dtype.names)], want)
    core = node.core
    if mode == "delegate":
        assert type(core) is NativeResidentCore
        core = core._delegate
    assert type(core) is ResidentWinSeqCore
    assert core._waker is None and core._watcher is None
    assert not tokens and seen == [0] * 12 and not watchers()
    assert (core.result_wakes, core.result_wake_rows) == (0, 0)
    assert "result_wakes" not in profile.counters()
    assert set(handed()) == {"svc"}
    if mode == "max_delay":
        assert not node._woken
    if mode == "recovery":
        assert node._wake is None and not ib._wake_armed
        # one emission a launch, in launch order: what the same core
        # driven by hand, with no node and no clock, emits
        by_hand = ResidentWinSeqCore(CB, Reducer("sum"), batch_len=64,
                                     flush_rows=FLUSH_ROWS)
        outs = [o for b in chunks for o in by_hand.process_batches(b)]
        outs.extend(by_hand.flush_batches())
        assert [len(b) for b in s.got] == [len(o) for o in outs if len(o)]


# -------------------------------------------------------- a failing fetch

def _fail_nth_fetch(ex, n):
    fetch, calls = ex._fetch, []

    def fetch_failing_nth(sel, out):
        calls.append(1)
        if len(calls) == n:
            raise RuntimeError("the wire broke")
        return fetch(sel, out)

    ex._fetch = fetch_failing_nth


def test_a_failing_fetch_with_no_further_input_fails_the_graph_at_once():
    chunks = cb_chunks(2, seed=21)
    surfaced = []

    def silence(s):
        if len(surfaced) == 1:          # after the second chunk: nothing more
            try:
                wait_for(s.df._failed.is_set, LONG / 2, "the failure")
                surfaced.append(True)
            except AssertionError:
                surfaced.append(False)
        else:
            surfaced.append(None)
            s.quiet()

    s = _PyStream(_stage("reducer"), chunks, silence, slow=True)
    # (the first chunk's eight launches are fetched, then one more)
    _fail_nth_fetch(s.cores[0].executor, N_KEYS + 2)
    with pytest.raises(RuntimeError, match="the wire broke"):
        s.run()
    # at the parent the failure waited for the stream's end
    assert surfaced == [None, True]
    # a graph that failed made no end-of-stream flush: its core's watcher
    # goes when the core does (below), here by hand
    s.cores[0]._stop_watcher()
    assert not watchers()


def test_a_failing_fetch_is_raised_once_by_collect_and_loses_no_window():
    chunk, = cb_chunks(1, seed=21)
    idle = threading.Event()
    core = slow_py_device(
        ResidentWinSeqCore(CB, Reducer("sum"), batch_len=1,
                           flush_rows=FLUSH_ROWS), idle.is_set)
    woken = []
    assert core.set_waker(lambda: woken.append(threading.current_thread()))
    _fail_nth_fetch(core.executor, 3)
    outs = [core.process(chunk)]
    assert len(outs[0]) == 0 and core.executor.dispatches == N_KEYS
    idle.set()
    # a wake a launch, each from the watcher's thread
    wait_for(lambda: len(woken) == N_KEYS, LONG, "the wakes")
    assert {t.name for t in woken} == {"wf-watch.0"}
    me = threading.current_thread()
    with pytest.raises(RuntimeError, match="the wire broke"):
        core.collect()                  # ... raised here, on this thread
    assert me is threading.current_thread() and me not in woken
    # raised once; the two launches fetched before it, the one that failed
    # and the five behind it are all still to be had
    outs.append(core.collect())
    assert len(outs[-1]) == 96
    assert len(core.collect()) == 0
    assert core.result_wakes == N_KEYS and core.result_wake_rows == 96
    outs.append(core.flush())
    got = np.sort(np.concatenate(outs), order=["key", "id"])
    assert_same(got, oracle(CB, Reducer("sum"), [chunk]))
    assert not watchers()


# ------------------------------------------------------ the watcher thread

def test_the_watcher_ends_with_the_stream_and_with_the_core():
    chunks = cb_chunks(3, seed=5)

    def make():
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            return ResidentWinSeqCore(CB, Reducer("sum"), batch_len=1,
                                      flush_rows=FLUSH_ROWS)

    before = threading.active_count()
    core = make()
    core.process(chunks[0])             # driven by hand: no waker, no thread
    assert threading.active_count() == before and not watchers()
    assert core.set_waker(lambda: None)
    assert threading.active_count() == before      # ... none before a launch
    core.process(chunks[1])
    assert len(watchers()) == 1
    core.flush()
    assert threading.active_count() == before and not watchers()
    # a second stream through the same core: a thread of its own, ended too
    assert core.set_waker(lambda: None)
    core.process(chunks[2])
    assert len(watchers()) == 1
    core.flush()
    assert threading.active_count() == before
    # dropped mid-stream, launches in flight or not: the thread goes
    for _ in range(20):
        core = make()
        assert core.set_waker(lambda: None)
        core.process(chunks[0])
    assert watchers()
    del core
    gc.collect()
    wait_for(lambda: threading.active_count() == before, LONG,
             "the dropped cores' watchers to end")
    # max_delay_ms keeps its timer: refused
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        timed = ResidentWinSeqCore(CB, Reducer("sum"), batch_len=1,
                                   max_delay_ms=5.0)
    assert not timed.set_waker(lambda: None)
    timed.process(chunks[0])
    assert timed._waker is None and not watchers()


# ------------------------------------------ the counters and the fields

def test_counters_and_record_fields_read_what_the_wake_took(tmp_path):
    chunks = cb_chunks(6, seed=13)
    s = _PyStream(_stage("reducer", batch_len=96), chunks, _PyStream.quiet,
                  slow=True, trace_dir=str(tmp_path))
    s.run()
    recs = [r for r in profile.records() if r[0] == "harvest_wait"]
    for _phase, _t0, _t1, _launch, _shard, _cause, extra, _cpu in recs:
        assert set(extra) == {"ready", "harvest", "handed"}
    how = handed()
    n_wake = how.count("wake")
    # a launch at every 96th fired window; the flush's is the drain's
    n_svc = len(how) - n_wake
    assert how == ["wake"] * n_wake + ["svc"] * n_svc
    assert n_wake >= 6 and n_svc >= 1
    assert [r[6]["harvest"] for r in recs] == (["poke"] * n_wake
                                               + ["drain"] * n_svc)
    counters = profile.counters()
    assert counters["result_wakes"] == n_wake
    assert counters["launches"] == len(how)
    # (what the end-of-stream flush drained is the sink's last batch)
    woken_rows = s.rows() - len(s.got[-1])
    assert counters["result_wake_rows"] == woken_rows
    log, = [json.loads(p.read_text()) for p in tmp_path.glob("*win_seq*")]
    assert log["result_wakes"] == n_wake
    assert log["result_wake_rows"] == woken_rows
    assert log["windows_fired"] == s.rows()
    # a wake is a call of the node's, with no rows
    assert log["rcv_batches"] > 6
    assert log["rcv_tuples"] == sum(len(b) for b in chunks)
    lines = [json.loads(ln) for ln in
             (tmp_path / "launches.jsonl").read_text().splitlines()]
    assert [ln["handed"] for ln in lines
            if ln["phase"] == "harvest_wait"] == how
