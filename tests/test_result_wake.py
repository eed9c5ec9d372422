"""A harvested result wakes its node (ISSUE 32): a window worker that sits
idle in ``inbox.get()`` emits what its ship thread just handed over, instead
of holding it in ``_out_q`` until the next input chunk.

* the inbox's ``wake()``: one token to an idle, armed inbox; none to a busy,
  a full, a closed or a finished one; never blocks, never raises, loses no
  item; many threads at once leave it able to wake;
* the receive loop: a token runs ``on_wake`` on the node's thread, booked as
  service; a channel's EOS that leaves others open serves a withheld wake;
  ``Comb`` hands the waker to its fused stages and forwards ``on_wake``;
* ONE chunk that closes windows and then a silent stream: the results reach
  the sink before the stream ends (the parent delivered them at its end);
* every core is oracle-equal under it, paced and unpaced, alone and in a
  two-worker ``WinFarmTPU`` behind its ordered collector;
* a ship-thread failure with no further input is raised once, by the wake,
  and no window is lost;
* recovery mode, ``overlap=False`` and ``max_delay_ms`` are sent no wake and
  emit as they did;
* ``result_wakes``, ``result_wake_rows`` and each ``harvest_wait`` record's
  ``handed`` / ``out_q_ms`` say what the wake took.
"""

import gc
import json
import sys
import threading
import time
import warnings
import weakref

import numpy as np
import pytest

from windflow_tpu.api.multipipe import MultiPipe
from windflow_tpu.ops.functions import Reducer
from windflow_tpu.patterns.basic import Sink, Source
from windflow_tpu.runtime import engine
from windflow_tpu.runtime.comb import Comb
from windflow_tpu.runtime.engine import Dataflow, Inbox, NativeInbox
from windflow_tpu.runtime.node import Node, SourceNode
from windflow_tpu.utils import profile

native = pytest.importorskip("windflow_tpu.native")
if not native.available():
    pytest.skip("native library unavailable", allow_module_level=True)

from test_flush_early import CB, N_KEYS, SCHEMA, settle  # noqa: E402
from test_flush_early import slow_device  # noqa: E402
from test_ship_harvest import (CORES, PARENT_EMISSIONS,  # noqa: E402
                               assert_same, cb_chunks, make_core, oracle,
                               wait_for)
from windflow_tpu.patterns.win_seq import (WinSeqNode,  # noqa: E402
                                           window_cores)
from windflow_tpu.patterns.win_seq_tpu import (WinFarmTPU,  # noqa: E402
                                               WinSeqTPU)

LONG = 60.0     # a deadline no healthy run comes near, xdist workers or not


@pytest.fixture(autouse=True)
def _profile_on(monkeypatch):
    monkeypatch.delenv("WF_PROFILE", raising=False)
    monkeypatch.delenv("WF_NO_OVERLAP", raising=False)
    profile.enable()
    profile.reset()
    yield
    profile.auto()
    profile.reset()


def handed():
    """The ``handed`` field of every launch a ship thread handed over."""
    return [r[6]["handed"] for r in profile.records()
            if r[0] == "harvest_wait" and "handed" in r[6]]


# ------------------------------------------------------------ the inbox

def make_inbox(kind, capacity=4, failed=None):
    if kind == "py":
        return Inbox(capacity, failed)
    return NativeInbox(capacity, failed, lib=native.enabled())


class _Quiet(dict):
    """A side table that reads empty: the emptiness test passed a moment
    before the ring filled."""

    def __bool__(self):
        return False


def _read_empty(ib):
    if isinstance(ib, Inbox):
        ib._q.empty = lambda: True
    else:
        ib._items = _Quiet(ib._items)


@pytest.mark.parametrize("kind", ["py", "native"])
def test_wake_queues_one_token_on_an_idle_armed_inbox(kind):
    ib = make_inbox(kind)
    ib.register_source()
    ib.wake()                           # nobody serves wakes yet
    assert ib.depth() == 0
    ib._wake_armed = True
    ib.wake()
    ib.wake()                           # one is outstanding
    assert ib.depth() == 1
    src, item = ib.get()
    assert item is engine._WAKE and src == -1
    assert ib.depth() == 0


@pytest.mark.parametrize("kind", ["py", "native"])
def test_wake_to_a_busy_or_full_inbox_does_nothing_and_loses_nothing(kind):
    ib = make_inbox(kind, capacity=2)
    ib.register_source()
    ib._wake_armed = True
    ib.put(0, "a")
    ib.wake()                           # not empty: its svc is on its way
    ib.put(0, "b")
    t0 = time.monotonic()
    ib.wake()                           # full
    _read_empty(ib)                     # ... and full behind a passed test
    ib.wake()
    assert time.monotonic() - t0 < 5
    assert ib._wake_armed               # a later wake is not locked out
    assert [ib.get(), ib.get()] == [(0, "a"), (0, "b")]
    assert len(ib._items if kind == "native" else ib._q.queue) == 0


@pytest.mark.parametrize("kind", ["py", "native"])
def test_wake_to_a_closed_inbox_returns_at_once(kind):
    failed = threading.Event()
    ib = make_inbox(kind, failed=failed)
    ib.register_source()
    ib._wake_armed = True
    failed.set()
    ib.cancel()
    t0 = time.monotonic()
    ib.wake()
    assert time.monotonic() - t0 < 5
    if kind == "native":                # the ring refused it: nothing kept
        assert not ib._items and ib._wake_armed


@pytest.mark.parametrize("kind", ["py", "native"])
def test_an_evicted_token_is_not_counted_as_shed(kind):
    from windflow_tpu.runtime.overload import OverloadPolicy
    if kind == "py":
        ib = Inbox(2, None, OverloadPolicy(shed="shed_oldest"))
    else:
        ib = NativeInbox(2, None, lib=native.enabled(),
                         policy=OverloadPolicy(shed="shed_oldest"))
    ib.register_source()
    ib._wake_armed = True
    ib.wake()
    ib.put(0, "a")
    ib.put(0, "b")                      # evicts the token
    assert ib.shed == 0 and ib._wake_armed
    ib.put(0, "c")                      # evicts "a"
    assert ib.shed == 1
    assert [ib.get(), ib.get()] == [(0, "b"), (0, "c")]


# ------------------------------------------------------ the receive loop

class _Feed(SourceNode):
    """Emits what the test hands it, ends when told."""

    def __init__(self, name="feed"):
        super().__init__(name)
        self.q = []
        self.cv = threading.Condition()
        self.done = False

    def push(self, item):
        with self.cv:
            self.q.append(item)
            self.cv.notify()

    def end(self):
        with self.cv:
            self.done = True
            self.cv.notify()

    def generate(self):
        while True:
            with self.cv:
                assert self.cv.wait_for(lambda: self.q or self.done, LONG)
                if not self.q:
                    return
                item = self.q.pop(0)
            self.emit(item)


class _Probe(Node):
    """Counts its calls; a svc waits for `gate` when one is set."""

    def __init__(self, name="probe"):
        super().__init__(name)
        self.svcs, self.wakes, self.threads = [], 0, set()
        self.gate = None
        self.in_svc = threading.Event()

    def svc(self, batch, channel=0):
        self.in_svc.set()
        if self.gate is not None:
            assert self.gate.wait(LONG)
        self.svcs.append(batch)

    def on_wake(self):
        self.wakes += 1
        self.threads.add(threading.current_thread().name)


def _graph(*feeds, probe=None, capacity=4, **kw):
    df = Dataflow("g", capacity=capacity, **kw)
    probe = df.add(probe or _Probe())
    for f in feeds:
        df.add(f)
        df.connect(f, probe)
    return df, probe


def _idle(df, node):
    """`node` sits in its inbox's get(): armed, nothing queued."""
    ib = df._inboxes[id(node)]
    wait_for(lambda: ib._wake_armed and ib.depth() == 0, LONG,
             "an idle, armed inbox")
    return ib


@pytest.mark.parametrize("capacity", [4, 0])    # the ring, the Python queue
def test_a_wake_runs_on_wake_on_the_nodes_thread_as_service(capacity,
                                                            tmp_path):
    feed = _Feed()
    df, probe = _graph(feed, capacity=capacity, trace_dir=str(tmp_path))
    df.run()
    _idle(df, probe)
    assert probe._wake is not None and feed._wake is None
    probe._wake()
    wait_for(lambda: probe.wakes == 1, LONG, "the wake")
    _idle(df, probe)
    probe._wake()                       # armed again
    wait_for(lambda: probe.wakes == 2, LONG, "the second wake")
    assert probe.threads == {"g/probe"}
    feed.push(np.zeros(3, dtype=SCHEMA.dtype()))
    feed.end()
    df.wait(timeout=LONG)
    # the token is no item of the stream: no rows, but a call of its own
    log = json.loads((tmp_path / "g_00_probe.log").read_text())
    assert log["rcv_batches"] == 3 and log["rcv_tuples"] == 3
    assert len(probe.svcs) == 1


def test_a_busy_node_is_sent_no_wake():
    feed = _Feed()
    df, probe = _graph(feed)
    probe.gate = threading.Event()
    df.run()
    batch = np.zeros(1, dtype=SCHEMA.dtype())
    feed.push(batch)
    assert probe.in_svc.wait(LONG)      # the node is inside a svc ...
    feed.push(batch)
    ib = df._inboxes[id(probe)]
    wait_for(lambda: ib.depth() == 1, LONG, "a queued chunk")
    for _ in range(100):
        probe._wake()                   # ... with its next one queued
    assert ib.depth() == 1
    probe.gate.set()
    feed.end()
    df.wait(timeout=LONG)
    assert probe.wakes == 0 and len(probe.svcs) == 2


def test_a_wake_after_the_nodes_eos_is_dropped():
    feed = _Feed()
    df, probe = _graph(feed)
    df.run()
    wake = probe._wake
    feed.end()
    df.wait(timeout=LONG)
    ib = df._inboxes[id(probe)]
    wake()
    assert ib.depth() == 0 and probe.wakes == 0
    # and it pins neither the graph nor the inbox
    refs = weakref.ref(df), weakref.ref(ib)
    del df, ib, probe, feed
    gc.collect()
    assert refs[0]() is None and refs[1]() is None
    wake()


def test_a_channels_eos_serves_a_withheld_wake():
    a, b = _Feed("a"), _Feed("b")
    df, probe = _graph(a, b)
    df.run()
    _idle(df, probe)
    a.end()                             # its EOS frame runs no svc
    wait_for(lambda: probe.wakes == 1, LONG, "on_wake after a's EOS")
    b.end()                             # the last one: eosnotify flushes
    df.wait(timeout=LONG)
    assert probe.wakes == 1


def test_a_supervised_node_is_never_armed():
    from windflow_tpu.recovery.policy import RecoveryPolicy
    feed = _Feed()
    probe = _Probe()
    probe.recoverable = True
    df, probe = _graph(feed, probe=probe, recovery=RecoveryPolicy())
    df.run()
    feed.push(np.zeros(2, dtype=SCHEMA.dtype()))
    wait_for(lambda: len(probe.svcs) == 1, LONG, "the batch")
    assert probe._recov is not None and probe._wake is None
    ib = df._inboxes[id(probe)]
    ib.wake()
    assert not ib._wake_armed and ib.depth() == 0
    feed.end()
    df.wait(timeout=LONG)
    assert probe.wakes == 0


def test_comb_hands_its_waker_down_and_forwards_on_wake():
    feed = _Feed()
    first, second = _Probe("first"), _Probe("second")
    first.svc = lambda batch, channel=0: first.emit(batch)
    df, comb = _graph(feed, probe=Comb([first, second], "fused"))
    df.run()
    _idle(df, comb)
    assert first._wake is comb._wake is second._wake is not None
    second._wake()                      # a fused stage's own thread asks
    wait_for(lambda: second.wakes == 1, LONG, "the fused stage's wake")
    assert first.wakes == 1
    assert first.threads == second.threads == {"g/fused"}
    feed.end()
    df.wait(timeout=LONG)


@pytest.mark.parametrize("kind", ["py", "native"])
def test_wakes_from_many_threads_leave_the_inbox_able_to_wake(kind):
    feed = _Feed()
    df, probe = _graph(feed, capacity=4 if kind == "native" else 0)
    df.run()
    ib = _idle(df, probe)
    assert isinstance(ib, NativeInbox if kind == "native" else Inbox)
    calls, stop = [0] * 16, time.monotonic() + 2.0
    batch = np.zeros(1, dtype=SCHEMA.dtype())

    def hammer(i):
        while time.monotonic() < stop:
            probe._wake()
            calls[i] += 1

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=hammer, args=(i,))
                   for i in range(len(calls))]
        for th in threads:
            th.start()
        for _ in range(50):             # the stream goes on underneath
            feed.push(batch)
            time.sleep(0.002)
        for th in threads:
            th.join(LONG)
            assert not th.is_alive()
    finally:
        sys.setswitchinterval(old)
    wait_for(lambda: len(probe.svcs) == 50, LONG, "every batch")
    assert 0 < probe.wakes <= sum(calls)
    # no update was lost: the inbox arms again and the next wake is served
    _idle(df, probe)
    before = probe.wakes
    probe._wake()
    wait_for(lambda: probe.wakes == before + 1, LONG, "a wake afterwards")
    feed.end()
    df.wait(timeout=LONG)


# ------------------------------------- one chunk, then a silent stream

class _Stream:
    """A pipe Source > `stage` > Sink over `chunks`.  Between two chunks,
    and after the last, `between(self)` runs on the source's thread; the
    sink notes what came and when."""

    def __init__(self, stage, chunks, between=None, fused_sink=False,
                 slow=False, schema=SCHEMA, **pipe_kw):
        self.chunks, self.between = chunks, between
        self.got, self.at = [], []
        self.ended = None
        self.pipe = MultiPipe("wake", **pipe_kw)
        self.pipe.add_source(Source(batches=self._gen(), schema=schema))
        self.pipe.add(stage)
        sink = Sink(self._sink, vectorized=True)
        (self.pipe.chain_sink if fused_sink else self.pipe.add_sink)(sink)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            self.df = self.pipe._build()
        self.cores = window_cores(self.df)
        # what each node was fed and what it has served, for quiet()
        self.fed = {id(n): 0 for n in self.df.nodes}
        self.done = dict(self.fed)
        for node in self.df.nodes:
            self._count(node, self.df._inboxes[id(node)])
            if slow:
                for core in _cores_of(node):
                    self._slow(core, node)

    def _slow(self, core, node):
        """`slow_device`, and no faster than the svc that made the launch:
        a launch's result lands when its node has gone back to its inbox,
        as on the chip at a paced rate — which call takes it out of _out_q
        is then no race."""
        for ex in slow_device(core).executors:
            def fetch(sel, out, fetch=ex._fetch):
                t_end = time.monotonic() + LONG / 4
                while (self.done[id(node)] != self.fed[id(node)]
                       and time.monotonic() < t_end):
                    time.sleep(0.0005)
                return fetch(sel, out)

            ex._fetch = fetch

    def _count(self, node, inbox):
        put, svc = inbox.put, node.svc

        def counted_put(src, item):
            self.fed[id(node)] += 1
            put(src, item)

        def counted_svc(batch, channel=0):
            svc(batch, channel)
            self.done[id(node)] += 1

        inbox.put, node.svc = counted_put, counted_svc

    def _gen(self):
        for b in self.chunks:
            yield b
            if self.between is not None:
                self.between(self)
        self.ended = time.monotonic()

    def _sink(self, batch):
        if batch is not None and len(batch):
            self.got.append(batch.copy())
            self.at.append(time.monotonic())

    def rows(self):
        return sum(len(b) for b in self.got)

    def run(self):
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            self.pipe.run_and_wait_end(timeout=4 * LONG)
        return np.sort(np.concatenate(self.got), order=["key", "id"])

    def quiet(self):
        """Every node has served what it was fed (twice the same reading:
        nothing moved in between), and every launch made is harvested,
        handed over and taken — by a wake, for every node sits idle."""
        last = [None]

        def served():
            before, last[0] = last[0], (dict(self.fed), dict(self.done))
            return before == last[0] and self.fed == self.done

        wait_for(served, LONG, "every node to have served its input")
        for core in self.cores:
            settle(core, LONG)
        # a count, not a look at the queues: a harvest takes its launch out
        # of flight before it puts the result into _out_q, so in between
        # nothing is in flight and _out_q reads empty, and the next chunk's
        # process() took what a wake should have.  A launch's record says
        # ``handed`` once the node thread has taken its result
        wait_for(lambda: len(handed()) == sum(ex.dispatches
                                              for core in self.cores
                                              for ex in core.executors),
                 LONG, "every launch to be handed over and taken")


def _cores_of(node):
    """The window cores `node` runs, fused stages included."""
    cores = [c for stage in getattr(node, "stages", ())
             for c in _cores_of(stage)]
    if isinstance(node, WinSeqNode):
        cores.append(node.core)
    return cores


def _cb_stage(**kw):
    kw.setdefault("batch_len", 1 << 20)
    kw.setdefault("flush_rows", 64 * N_KEYS)
    return WinSeqTPU(Reducer("sum"), CB.win_len, CB.slide_len, CB.win_type,
                     **kw)


@pytest.mark.parametrize("fused_sink", [False, True])
@pytest.mark.parametrize("slow", [False, True])
def test_one_chunk_then_silence_delivers_before_the_stream_ends(slow,
                                                                fused_sink):
    chunk, = cb_chunks(1)
    want = oracle(CB, Reducer("sum"), [chunk])
    closed = 96         # of its 128 windows the chunk itself closes these
    seen = []

    def silence(s):
        # nothing more comes; at the parent the launch's result lay in
        # _out_q until the end-of-stream flush, however long this took
        try:
            wait_for(lambda: s.rows() >= closed, LONG / 2, "the results")
            seen.append(s.rows())
        except AssertionError:
            seen.append(None)

    s = _Stream(_cb_stage(), [chunk], silence, fused_sink=fused_sink,
                slow=slow)
    got = s.run()
    assert seen == [closed], "the result waited for the stream's end"
    assert s.at[0] < s.ended
    assert_same(got, want)
    if slow:
        # the chunk's launch, then the flush's.  (A launch that is ready at
        # the poll after its dispatch, as on this CPU, can reach _out_q
        # before the chunk's own process() drains it.)
        assert handed() == ["wake", "svc"]
        counters = profile.counters()
        assert counters["result_wakes"] == 1
        assert counters["result_wake_rows"] == closed
        core, = s.cores
        assert (core.result_wakes, core.result_wake_rows) == (1, closed)


# ------------------------------------------------------ oracle-equal cores

def _stage_of(name, farm):
    spec, fn, _make, kw = CORES[name]
    kw = dict(kw)
    kw.setdefault("batch_len", 1 << 20)
    kw.setdefault("flush_rows", 64 * N_KEYS // kw.get("shards", 1))
    if farm:
        kw.pop("shards", None)          # a farm worker has one ring
        return WinFarmTPU(fn(), spec.win_len, spec.slide_len, spec.win_type,
                          pardegree=2, **kw)
    return WinSeqTPU(fn(), spec.win_len, spec.slide_len, spec.win_type, **kw)


def _chunks_of(name):
    _spec, _fn, make, _kw = CORES[name]
    return make(24) if make is cb_chunks else make()


@pytest.mark.parametrize("paced", [False, True])
@pytest.mark.parametrize("name", list(CORES))
def test_every_core_is_oracle_equal_under_the_wake(name, paced):
    spec, fn, _make, _kw = CORES[name]
    chunks = _chunks_of(name)
    s = _Stream(_stage_of(name, farm=False), chunks,
                between=_Stream.quiet if paced else None,
                slow=paced)
    got = s.run()
    # (the sink's rows carry the schema of the stage's result)
    want = oracle(spec, fn(), chunks)
    assert_same(got[list(want.dtype.names)], want)
    how = handed()
    assert how and set(how) <= {"wake", "svc"}
    counters = profile.counters()
    assert counters.get("result_wakes", 0) == how.count("wake")
    assert counters["launches"] == len(how)
    if paced:
        # every launch but what the end of the stream drained left with a
        # wake, before the next chunk came
        core, = s.cores
        assert how.count("wake") >= len(how) - 2 * core.shards
        assert (core.result_wakes, core.result_wake_rows) == (
            counters["result_wakes"], counters["result_wake_rows"])


@pytest.mark.parametrize("paced", [False, True])
@pytest.mark.parametrize("name", ["cb_sum-1", "tb_multi-1"])
def test_a_two_worker_farm_is_oracle_equal_and_in_order(name, paced):
    spec, fn, _make, _kw = CORES[name]
    chunks = _chunks_of(name)
    s = _Stream(_stage_of(name, farm=True), chunks,
                between=_Stream.quiet if paced else None,
                slow=paced)
    assert len(s.cores) == 2
    got = s.run()
    want = oracle(spec, fn(), chunks)
    assert_same(got[list(want.dtype.names)], want)
    # behind the ordered collector every key's windows arrive in order
    arrived = np.concatenate(s.got)
    for k in np.unique(arrived["key"]):
        ids = arrived["id"][arrived["key"] == k]
        assert (np.diff(ids) > 0).all()
    if paced:
        assert handed().count("wake") >= len(handed()) - 4
        assert sum(c.result_wakes for c in s.cores) == \
            handed().count("wake")


# ------------------------------------------------- a failing ship thread

def test_a_ship_failure_with_no_further_input_fails_the_graph_at_once():
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

    s = _Stream(_cb_stage(), chunks, silence, slow=True)
    ex = s.cores[0].executor
    fetch, calls = ex._fetch, []

    def fetch_failing_second(sel, out):
        calls.append(1)
        if len(calls) == 2:
            raise RuntimeError("the wire broke")
        return fetch(sel, out)

    ex._fetch = fetch_failing_second
    with pytest.raises(RuntimeError, match="the wire broke"):
        s.run()
    # at the parent the failure lay in _ship_exc until the stream's end
    assert surfaced == [None, True]


def test_a_failure_is_raised_once_by_collect_and_loses_no_window():
    chunk, = cb_chunks(1, seed=21)
    core = slow_device(make_core(shards=2))
    woken = []
    assert core.set_waker(lambda: woken.append(1))
    ex = core.executors[0]
    fetch, failed = ex._fetch, []

    def fetch_failing_once(sel, out):
        if not failed:
            failed.append(1)
            raise RuntimeError("the wire broke")
        return fetch(sel, out)

    ex._fetch = fetch_failing_once
    # no fetch returns or fails before process() has: a ship thread that
    # got there first had process() itself raise the failure, or take the
    # other shard's result, at its closing drain
    back = threading.Event()
    for ex in core.executors:
        def gated(sel, out, fetch=ex._fetch):
            assert back.wait(LONG)
            return fetch(sel, out)

        ex._fetch = gated
    outs = [core.process(chunk)]
    assert len(outs[0]) == 0
    back.set()
    # shard 0's wait failed, shard 1's handed its launch over: a wake each
    wait_for(lambda: core._ship_exc is not None
             and core._out_q.qsize() == 1 and len(woken) >= 2, LONG,
             "the failure and the other shard's result")
    with pytest.raises(RuntimeError, match="the wire broke"):
        core.collect()
    outs.append(core.collect())         # raised once; what it drained, kept
    assert len(outs[-1]) and core._ship_exc is None
    outs.append(core.flush())           # the launch it could not fetch
    core._stop_worker()
    got = np.sort(np.concatenate(outs), order=["key", "id"])
    assert_same(got, oracle(CB, Reducer("sum"), [chunk]))


# ------------------------ the paths that are sent no wake, as they were

@pytest.mark.parametrize("mode", ["sync", "max_delay", "recovery"])
def test_a_core_off_the_clock_takes_no_waker_and_emits_as_before(mode):
    chunks = cb_chunks(26, chunk_ids=32, seed=7)
    want = oracle(CB, Reducer("sum"), chunks)
    kw = {"sync": dict(overlap=False, max_delay_ms=600_000.0),
          "max_delay": dict(overlap=True, max_delay_ms=600_000.0),
          "recovery": dict(overlap=True)}[mode]
    core = make_core(flush_rows=2048, **kw)
    woken = []
    took = core.set_waker(lambda: woken.append(1))
    # (a core learns of recovery at its first process_batches: it gives the
    # waker back then, before any launch)
    assert took == (mode == "recovery")
    outs = []
    for b in chunks:
        if mode == "recovery":
            outs.extend(core.process_batches(b))
        else:
            outs.append(core.process(b))
            settle(core, LONG)
    assert core._waker is None
    assert len(core.collect()) == 0 or mode == "max_delay"
    outs.extend(core.flush_batches() if mode == "recovery"
                else [core.flush()])
    core._stop_worker()
    assert not woken and "result_wakes" not in profile.counters()
    assert_same(np.sort(np.concatenate(outs), order=["key", "id"]), want)
    if mode != "max_delay":     # (that one's polls follow the ship thread)
        assert [len(o) for o in outs if len(o)] == PARENT_EMISSIONS


@pytest.mark.parametrize("mode", ["sync", "recovery"])
def test_a_graph_off_the_clock_queues_no_token_and_groups_as_before(mode):
    from windflow_tpu.recovery.policy import RecoveryPolicy
    chunks = cb_chunks(26, chunk_ids=32, seed=7)
    want = oracle(CB, Reducer("sum"), chunks)
    tokens = []
    stage = _cb_stage(flush_rows=2048,
                      **(dict(max_delay_ms=600_000.0)
                         if mode == "sync" else {}))
    s = _Stream(stage, chunks,
                **(dict(recovery=RecoveryPolicy())
                   if mode == "recovery" else {}))
    node, = [n for n in s.df.nodes if isinstance(n, WinSeqNode)]
    ib = s.df._inboxes[id(node)]
    if mode == "sync":
        node.core._overlap = False      # as WF_NO_OVERLAP: no ship thread
        node.core._stop_worker()
    wake = ib.wake
    ib.wake = lambda: (tokens.append(1), wake())
    got = s.run()
    assert_same(got, want)
    assert not tokens and not node._woken
    assert "result_wakes" not in profile.counters()
    assert handed() == [] or set(handed()) == {"svc"}
    if mode == "recovery":
        assert node._wake is None and not ib._wake_armed
        # one emission a launch, in launch order: the parent's grouping
        assert [len(b) for b in s.got] == PARENT_EMISSIONS


# ------------------------------------------ the counters and the fields

def test_counters_and_record_fields_read_what_the_wake_took(tmp_path):
    chunks = cb_chunks(6, seed=13)
    s = _Stream(_cb_stage(), chunks, _Stream.quiet, slow=True,
                trace_dir=str(tmp_path))
    s.run()
    recs = [r for r in profile.records() if r[0] == "harvest_wait"]
    assert len(recs) == 7
    for _phase, _t0, t1, _launch, _shard, _cause, extra, _cpu in recs:
        assert set(extra) == {"ready", "harvest", "handed", "out_q_ms"}
        assert 0 <= extra["out_q_ms"] < 1e3 * LONG
    assert handed() == ["wake"] * 6 + ["svc"]
    counters = profile.counters()
    assert counters["result_wakes"] == 6 and counters["launches"] == 7
    # (the end-of-stream flush's launch is the sink's last batch)
    woken_rows = s.rows() - len(s.got[-1])
    assert len(s.got) == 7 and counters["result_wake_rows"] == woken_rows
    log, = [json.loads(p.read_text()) for p in tmp_path.glob("*win_seq*")]
    assert log["result_wakes"] == 6
    assert log["result_wake_rows"] == woken_rows
    # a wake is a call of the node's, with no rows
    assert log["rcv_batches"] >= 6 + 6
    assert log["rcv_tuples"] == sum(len(b) for b in chunks)
    # the file the engine writes carries the two fields
    lines = [json.loads(ln) for ln in
             (tmp_path / "launches.jsonl").read_text().splitlines()]
    assert [ln["handed"] for ln in lines
            if ln["phase"] == "harvest_wait"] == ["wake"] * 6 + ["svc"]
    assert all("out_q_ms" in ln for ln in lines
               if ln["phase"] == "harvest_wait")
