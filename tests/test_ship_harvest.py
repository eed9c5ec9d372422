"""A ship thread with nothing to send waits on its oldest launch (ISSUE 29):
the result of a launch reaches ``_out_q`` when it lands, not at the node
thread's next poke, and the executor's measured service is the ring's own.

* after ONE ``process()`` and no further call the result is in ``_out_q``;
* ``mean_service_s()`` does not grow with the pause between calls;
* every core is oracle-equal under it: shards 1 / 2, CB ``sum``, a TB
  multi-field aggregate, the arg-extremum core;
* a failure inside a self-driven harvest is raised once at the next
  ``process()`` and no window is lost;
* a dropped core ends its ship threads, and ``_stop_worker`` returns while
  a wait is open;
* the synchronous path and recovery mode never wait: the launches and
  per-launch emissions they made before;
* the ``harvest`` field of every ``harvest_wait`` record and the
  ``harvest_waited`` counter say which harvest it was.
"""

import gc
import threading
import time
import warnings
import weakref

import numpy as np
import pytest

from windflow_tpu.core.tuples import Schema, batch_from_columns
from windflow_tpu.core.windows import WindowSpec, WinType
from windflow_tpu.core.winseq import WinSeqCore
from windflow_tpu.ops.functions import ArgReducer, MultiReducer, Reducer
from windflow_tpu.utils import profile

native = pytest.importorskip("windflow_tpu.native")
if not native.available():
    pytest.skip("native library unavailable", allow_module_level=True)

from test_flush_early import (CB, N_KEYS, settle,  # noqa: E402
                              slow_device)
from test_flush_early import cb_chunks as _cb_chunks  # noqa: E402
from windflow_tpu.patterns.native_core import NativeResidentCore  # noqa: E402

CHUNK_IDS = 64                       # ids a key a chunk
FLUSH_ROWS = CHUNK_IDS * N_KEYS      # every chunk launches naturally, once


@pytest.fixture(autouse=True)
def _profile_on(monkeypatch):
    monkeypatch.delenv("WF_PROFILE", raising=False)
    monkeypatch.delenv("WF_NO_OVERLAP", raising=False)
    profile.enable()
    profile.reset()
    yield
    profile.auto()
    profile.reset()


def cb_chunks(n_chunks, chunk_ids=CHUNK_IDS, seed=0):
    return _cb_chunks(n_chunks, chunk_ids, seed)


def make_core(spec=CB, fn=None, **kw):
    kw.setdefault("batch_len", 1 << 20)
    # (the eight keys fall four and four on two shards)
    kw.setdefault("flush_rows", FLUSH_ROWS // kw.get("shards", 1))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        return NativeResidentCore(spec, fn or Reducer("sum"), **kw)


def wait_for(cond, timeout=30.0, what="the condition"):
    t_end = time.monotonic() + timeout
    while time.monotonic() < t_end:
        if cond():
            return
        time.sleep(0.0005)
    raise AssertionError(f"{what} never came")


def oracle(spec, fn, chunks):
    host = WinSeqCore(spec, fn)
    outs = [host.process(b) for b in chunks] + [host.flush()]
    return np.sort(np.concatenate(outs), order=["key", "id"])


def run(core, chunks, between=None):
    outs = []
    for b in chunks:
        outs.append(core.process(b))
        if between is not None:
            between()
    outs.append(core.flush())
    core._stop_worker()
    return np.sort(np.concatenate(outs), order=["key", "id"])


def assert_same(a, b):
    assert len(a) == len(b)
    for f in a.dtype.names:
        np.testing.assert_array_equal(a[f], b[f], err_msg=f)


def harvests():
    """The ``harvest`` field of every closed launch, in closing order."""
    return [r[6]["harvest"] for r in profile.records()
            if r[0] == "harvest_wait"]


def takes():
    return [r[6]["trigger"] for r in profile.records()
            if r[0] == "launch_take"]


# ------------------------------------------- the result comes without a poke

@pytest.mark.parametrize("shards", [1, 2])
def test_one_process_call_delivers_its_result(shards):
    chunk, = cb_chunks(1)
    core = slow_device(make_core(shards=shards), 0.01)
    assert len(core.process(chunk)) == 0      # dispatched a moment ago
    # no further call: at the parent the launch lay in flight until the
    # next poke, however long that took to come
    wait_for(lambda: core._out_q.qsize() == shards,
             what="the launches' results in _out_q")
    assert not any(ex._inflight for ex in core.executors)
    assert harvests() == ["wait"] * shards
    counters = profile.counters()
    assert counters["harvest_waited"] == counters["launches"] == shards
    got = np.sort(np.concatenate([core.process(chunk[:0]), core.flush()]),
                  order=["key", "id"])
    core._stop_worker()
    assert_same(got, oracle(CB, Reducer("sum"), [chunk]))


def test_service_does_not_grow_with_the_pause_between_calls():
    pause = 0.05
    chunks = cb_chunks(12, seed=3)
    want = oracle(CB, Reducer("sum"), chunks)
    # the step shapes compiled beforehand: a compile is no launch service
    assert_same(run(make_core(), chunks), want)
    profile.reset()
    core = slow_device(make_core(), 0.005)
    ex = core.executor
    got = run(core, chunks, between=lambda: time.sleep(pause))
    assert_same(got, want)
    # dispatch -> harvest of a 5 ms step; harvested at the next poke it
    # read the pause, 50 ms
    assert 0.005 <= ex.mean_service_s() < pause / 2
    n = len(harvests())
    assert harvests().count("wait") >= n - 2
    assert profile.counters()["harvest_waited"] == harvests().count("wait")


# ------------------------------------------------------ oracle-equal cores

MF_SCHEMA = Schema(rev=np.int64, amt=np.int64)
BID_SCHEMA = Schema(price=np.int64, auction=np.int64)


def _mf_fn():
    return MultiReducer(("count", None, "n"), ("max", "ts", "last"),
                        ("sum", "rev", "rsum"), ("max", "amt", "ahi"))


def _arg_fn():
    return MultiReducer(
        ArgReducer("max", "price", id_out="bid",
                   carry=("auction", ("ts", "when")), value_range=(0, 1000)),
        Reducer("count", out_field="count"))


def _tb_mf_chunks(n_chunks=30, per=40, n_keys=4, seed=5):
    rng = np.random.default_rng(seed)
    out = []
    for c in range(n_chunks):
        t = np.repeat(np.arange(c * per, (c + 1) * per), n_keys)
        out.append(batch_from_columns(
            MF_SCHEMA, key=np.tile(np.arange(n_keys), per), id=t, ts=t * 7,
            rev=rng.integers(0, 50, size=len(t)).astype(np.int64),
            amt=rng.integers(-9000, 9000, size=len(t)).astype(np.int64)))
    return out


def _bid_chunks(n_chunks=24, per=300, seed=9):
    rng = np.random.default_rng(seed)
    n = n_chunks * per
    b = batch_from_columns(
        BID_SCHEMA, key=np.zeros(n), id=np.arange(n),
        ts=np.sort(rng.integers(0, n // 2, n)),
        price=rng.integers(0, 60, n), auction=rng.integers(0, 99, n))
    return [b[i:i + per] for i in range(0, n, per)]


CORES = {
    "cb_sum-1": (CB, lambda: Reducer("sum"), cb_chunks, dict(shards=1)),
    "cb_sum-2": (CB, lambda: Reducer("sum"), cb_chunks, dict(shards=2)),
    "tb_multi-1": (WindowSpec(700, 350, WinType.TB), _mf_fn, _tb_mf_chunks,
                   dict(shards=1, batch_len=64, flush_rows=150)),
    "tb_multi-2": (WindowSpec(700, 350, WinType.TB), _mf_fn, _tb_mf_chunks,
                   dict(shards=2, batch_len=64, flush_rows=150)),
    "argext": (WindowSpec(500, 500, WinType.TB), _arg_fn, _bid_chunks,
               dict(batch_len=1, flush_rows=64)),
}


@pytest.mark.parametrize("paced", [False, True])
@pytest.mark.parametrize("name", list(CORES))
def test_every_core_is_oracle_equal(name, paced):
    spec, fn, make, kw = CORES[name]
    chunks = make(24) if make is cb_chunks else make()
    core = make_core(spec, fn(), **kw)
    if paced:
        slow_device(core)
    # paced: every launch is harvested by its ship thread's own wait before
    # the next chunk comes; unpaced, on the CPU: polls and waits interleave
    got = run(core, chunks,
              between=(lambda: settle(core)) if paced else None)
    assert_same(got, oracle(spec, fn(), chunks))
    how = harvests()
    assert how and set(how) <= {"wait", "poke", "depth", "drain"}
    counters = profile.counters()
    assert counters["launches"] == len(how) == len(takes())
    assert counters.get("harvest_waited", 0) == how.count("wait")
    if paced:
        # all but what the end of the stream drained
        assert how.count("wait") >= len(how) - 2 * core.shards


# ------------------------------------------------- a failing harvest

def test_a_failed_wait_is_raised_once_and_loses_no_window():
    chunks = cb_chunks(10, seed=21)
    core = slow_device(make_core())
    ex = core.executor
    fetch, failed = ex._fetch, []

    def fetch_failing_once(sel, out):
        if not failed:
            failed.append(threading.current_thread().name)
            raise RuntimeError("the wire broke")
        return fetch(sel, out)

    ex._fetch = fetch_failing_once
    outs = [core.process(chunks[0])]
    wait_for(lambda: core._ship_exc is not None, what="the failure")
    # on the ship thread, in a wait of its own
    assert failed == [f"wf-ship.{core._shard_base}"]
    assert harvests() == ["wait"]
    # the thread does not spin on the launch it could not fetch
    time.sleep(0.05)
    assert len(failed) == 1 and len(ex._inflight) == 1
    with pytest.raises(RuntimeError, match="the wire broke"):
        core.process(chunks[1])
    for b in chunks[2:]:
        outs.append(core.process(b))        # raised once
    outs.append(core.flush())
    core._stop_worker()
    got = np.sort(np.concatenate(outs), order=["key", "id"])
    assert_same(got, oracle(CB, Reducer("sum"), chunks))


# ------------------------------------------------------ the thread's life

class _HeldFetch:
    """An executor's `_fetch` that blocks until released."""

    def __init__(self, ex):
        self.entered, self.release = threading.Event(), threading.Event()
        self._fetch = ex._fetch
        ex._fetch = self
        ex._is_ready = lambda out: False    # not at the poll: slow_device

    def __call__(self, sel, out):
        self.entered.set()
        assert self.release.wait(30)
        return self._fetch(sel, out)


def test_a_dropped_core_ends_its_ship_threads():
    core = make_core(shards=2)
    threads = list(core._ship_threads)
    ref = weakref.ref(core)
    core.process(cb_chunks(1)[0])
    settle(core)
    del core
    # neither a wait nor its result pins it (a ship thread holds it over
    # the handing over of a result, no longer)
    wait_for(lambda: ref() is None, what="the core's end")
    for th in threads:
        th.join(timeout=10)
        assert not th.is_alive()


def test_a_core_dropped_during_a_wait_ends_its_ship_thread():
    core = make_core()
    th, = core._ship_threads
    held = _HeldFetch(core.executor)
    core.process(cb_chunks(1)[0])
    assert held.entered.wait(30)
    # through the wait the thread holds the executor alone
    core._stop_worker = lambda: None        # the drop itself, no join
    ref = weakref.ref(core)
    del core
    gc.collect()
    assert ref() is None
    held.release.set()
    th.join(timeout=10)
    assert not th.is_alive()


def test_stop_worker_returns_while_a_wait_is_open():
    core = make_core()
    th, = core._ship_threads
    held = _HeldFetch(core.executor)
    core.process(cb_chunks(1)[0])
    assert held.entered.wait(30)
    threading.Timer(0.2, held.release.set).start()
    t0 = time.monotonic()
    core._stop_worker()             # its None is read when the wait returns
    assert time.monotonic() - t0 < 8
    assert not th.is_alive()
    # what the wait harvested was handed over before the thread ended
    assert core._out_q.qsize() == 1


# ------------------------------- the paths that never wait, as they were

#: launches (trigger) and rows per emission of 26 chunks of 256 rows at
#: flush_rows 2048, read at the parent commit (9b1e672) from these very calls
PARENT_TRIGGERS = ["natural"] * 3 + ["eos"]
PARENT_EMISSIONS = [480, 512, 512, 160]


@pytest.mark.parametrize("mode", ["sync", "recovery"])
def test_sync_and_recovery_launch_and_emit_as_before(mode):
    chunks = cb_chunks(26, chunk_ids=32, seed=7)
    want = oracle(CB, Reducer("sum"), chunks)
    # (the synchronous path's early flush follows the clock: its launches
    # are pinned under a budget no run reaches, tests/test_flush_early.py)
    kw = (dict(overlap=True) if mode == "recovery"
          else dict(overlap=False, max_delay_ms=600_000.0))
    for _again in range(2):
        profile.reset()
        core = make_core(flush_rows=2048, **kw)
        outs = []
        for b in chunks:
            if mode == "recovery":
                outs.extend(core.process_batches(b))
            else:
                outs.append(core.process(b))
                wait_for(core.executor.ring_idle)
        outs.extend(core.flush_batches() if mode == "recovery"
                    else [core.flush()])
        assert not core._overlap
        assert not getattr(core, "_ship_threads", ())
        assert_same(np.sort(np.concatenate(outs), order=["key", "id"]), want)
        assert takes() == PARENT_TRIGGERS
        assert "wait" not in harvests()
        assert "harvest_waited" not in profile.counters()
        assert [len(o) for o in outs if len(o)] == PARENT_EMISSIONS


# ------------------------------------------------ the field and the counter

def test_harvest_field_names_what_made_the_thread_harvest():
    chunks = cb_chunks(6, seed=13)
    # synchronous: a poll finds a result ready (poke) or the end drains it
    core = make_core(overlap=False)
    for b in chunks:
        core.process(b)
        wait_for(core.executor.ring_idle)
    core.flush()
    how = harvests()
    # (the last chunk's launch: at the poll that follows its dispatch, or not)
    assert how[:5] == ["poke"] * 5 and how[5:] in (["poke", "drain"],
                                                   ["drain", "drain"])
    assert "harvest_waited" not in profile.counters()
    # more launches in one call than the executor keeps in flight
    profile.reset()
    core = make_core(overlap=False, depth=1)
    core.process(np.concatenate(chunks))
    # (queued launches coalesce; the newest may be ready at the call's poll)
    assert "depth" in harvests() and set(harvests()) <= {"depth", "poke"}
    core.flush()
    # a ship thread between pokes: its own wait
    profile.reset()
    core = slow_device(make_core())
    for b in chunks:
        core.process(b)
        settle(core)
    core.flush()
    core._stop_worker()
    assert harvests() == ["wait"] * 6 + ["drain"]
    counters = profile.counters()
    assert counters["harvest_waited"] == 6 and counters["launches"] == 7
    by_launch = {}
    for phase, _t0, _t1, launch, _shard, _cause, extra, _cpu in \
            profile.records():
        if phase == "harvest_wait":
            # (and, since ISSUE 32, which call took it out of _out_q)
            assert set(extra) == {"ready", "harvest", "handed", "out_q_ms"}
            assert extra["handed"] == "svc"
            by_launch[launch] = extra["harvest"]
    assert len(by_launch) == 7
