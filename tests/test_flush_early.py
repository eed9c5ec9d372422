"""The device-following flush (ISSUE 27): below ``flush_rows`` / ``batch_len``
a native core ships what it holds when a fired window waits, the call made no
natural launch, and its ring is idle — and only then.

Every case is oracle-equal (the host ``WinSeqCore`` on the same stream) and
counts ``flush_early`` from ``profile.counters()``:

* chunks under ``flush_rows`` with the ring idle between them engage it, once
  a natural launch has set ring and shape, and an early launch ships the
  natural launch's rectangle (no step shape of its own);
* chunks of ``flush_rows`` launch naturally in every call: never;
* a time-based stream between closes holds no fired window: never, and at
  once after a close;
* recovery mode and ``max_delay_ms`` keep the launches they made before;
* a busy ring, and the cost guard under a slow measured service, hold it
  back: the service is the executor's own, measured by a ship thread that
  waits on its launches (ISSUE 29).
"""

import threading
import time
import warnings

import numpy as np
import pytest

from windflow_tpu.core.tuples import Schema, batch_from_columns
from windflow_tpu.core.windows import WindowSpec, WinType
from windflow_tpu.core.winseq import WinSeqCore
from windflow_tpu.ops.functions import Reducer
from windflow_tpu.utils import profile

native = pytest.importorskip("windflow_tpu.native")
if not native.available():
    pytest.skip("native library unavailable", allow_module_level=True)

from windflow_tpu.patterns import native_core  # noqa: E402
from windflow_tpu.patterns.native_core import NativeResidentCore  # noqa: E402

SCHEMA = Schema(value=np.int64)
N_KEYS = 8
CHUNK_IDS = 32                       # ids a key a chunk
CHUNK_ROWS = CHUNK_IDS * N_KEYS      # 256
FLUSH_ROWS = 8 * CHUNK_ROWS          # a natural launch every 8th chunk
CB = WindowSpec(16, 4, WinType.CB)


@pytest.fixture(autouse=True)
def _profile_on(monkeypatch):
    monkeypatch.delenv("WF_PROFILE", raising=False)
    profile.enable()
    profile.reset()
    yield
    profile.auto()
    profile.reset()


def cb_chunks(n_chunks, chunk_ids=CHUNK_IDS, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for c in range(n_chunks):
        ids = np.repeat(np.arange(c * chunk_ids, (c + 1) * chunk_ids), N_KEYS)
        keys = np.tile(np.arange(N_KEYS), chunk_ids)
        vals = rng.integers(-50, 100, size=len(ids)).astype(np.int64)
        out.append(batch_from_columns(SCHEMA, key=keys, id=ids, ts=ids,
                                      value=vals))
    return out


def tb_chunks(n_chunks, ts_per_chunk=10, n_keys=4, seed=0):
    """Every key gets one row a time unit; ids run on per key."""
    rng = np.random.default_rng(seed)
    out = []
    for c in range(n_chunks):
        ts = np.repeat(np.arange(c * ts_per_chunk, (c + 1) * ts_per_chunk),
                       n_keys)
        keys = np.tile(np.arange(n_keys), ts_per_chunk)
        vals = rng.integers(0, 100, size=len(ts)).astype(np.int64)
        out.append(batch_from_columns(SCHEMA, key=keys, id=ts, ts=ts,
                                      value=vals))
    return out


def make_core(spec=CB, service_s=0.0, **kw):
    """A native core whose executors report `service_s` as their mean launch
    service (None: what they measure — on a cold cache that is the compile)."""
    kw.setdefault("batch_len", 1 << 20)
    kw.setdefault("flush_rows", FLUSH_ROWS)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        core = NativeResidentCore(spec, Reducer("sum"), **kw)
    if service_s is not None:
        for ex in core.executors:
            ex.mean_service_s = lambda s=service_s: s
    return core


def slow_device(core, step_s=0.0):
    """Make every launch of `core` what it is on the chip: not ready at the
    poll that follows its dispatch (a CPU step of these sizes is), and
    fetched `step_s` later.  Only a blocking fetch then harvests it."""
    for ex in core.executors:
        def fetch(sel, out, fetch=ex._fetch):
            time.sleep(step_s)
            return fetch(sel, out)

        def is_ready(out, ex=ex):       # once it is harvested
            return not any(e[2] is out for e in list(ex._inflight))

        ex._fetch, ex._is_ready = fetch, is_ready
    return core


def settle(core, timeout=30.0):
    """Wait until the executor is idle between two chunks: every ship thread
    has worked off the tokens it was sent (one more, with an event, closes
    the gap between `launch_take` and the dispatch, where nothing is pending
    and nothing in flight yet), then nothing is queued, nothing is in flight
    — a ship thread harvests its launches itself — and every ring has
    served what it was sent.  The synchronous path launches inside
    `process()` and harvests at its next poll, so there the ring alone says."""
    t_end = time.monotonic() + timeout
    if core._overlap:
        evs = [threading.Event() for _ in core._ship_qs]
        for q, ev in zip(core._ship_qs, evs):
            q.put(("ship", ev))
        for ev in evs:
            assert ev.wait(timeout), "a ship thread never answered"
    while time.monotonic() < t_end:
        if (all(core._lib.wf_launch_pending(h) == 0 for h in core._hs)
                and not (core._overlap
                         and any(ex._inflight for ex in core.executors))
                and all(ex.ring_idle() for ex in core.executors)):
            return
        time.sleep(0.0005)
    raise AssertionError("the ring never went idle")


def early():
    return int(profile.counters().get("flush_early", 0))


def feed(core, chunks, idle_between=True, after_chunk=None):
    outs = []
    for i, b in enumerate(chunks):
        outs.append(core.process(b))
        if idle_between:
            settle(core)
        if after_chunk is not None:
            after_chunk(i)
    outs.append(core.flush())
    core._stop_worker()
    return np.sort(np.concatenate(outs), order=["key", "id"])


def oracle(spec, chunks):
    host = WinSeqCore(spec, Reducer("sum"))
    outs = [host.process(b) for b in chunks] + [host.flush()]
    return np.sort(np.concatenate(outs), order=["key", "id"])


def assert_same(a, b):
    assert len(a) == len(b)
    for f in ("key", "id", "ts", "value"):
        np.testing.assert_array_equal(a[f], b[f])


def takes():
    """(trigger, rows_live, rows_shipped) of every launch, in ship order."""
    return [(r[6]["trigger"], r[6]["rows_live"], r[6]["rows_shipped"])
            for r in profile.records() if r[0] == "launch_take"]


# ----------------------------------------------------------- (a) it engages

@pytest.mark.parametrize("shards,overlap", [(1, True), (1, False), (2, True)])
def test_small_chunks_on_an_idle_ring_flush_early(shards, overlap):
    chunks = cb_chunks(40, seed=shards)
    want = oracle(CB, chunks)
    # the same stream with early flushing impossible: one chunk >= flush_rows
    whole = feed(make_core(shards=shards, overlap=overlap),
                 [np.concatenate(chunks)])
    assert early() == 0
    assert_same(whole, want)

    profile.reset()
    seen = []
    got = feed(make_core(shards=shards, overlap=overlap), chunks,
               after_chunk=lambda i: seen.append(early()))
    assert_same(got, want)
    n_early = early()
    assert n_early > 0
    assert profile.counters()["flush_early_rows"] > 0
    # not before a natural launch has set the ring and the shape to borrow
    first_natural = FLUSH_ROWS // CHUNK_ROWS
    assert seen[first_natural - 2] == 0
    if shards == 1:
        assert seen[first_natural - 1] == 0
        assert seen[first_natural] == 1
        # from then on every chunk: flush_rows is never reached again
        assert n_early == len(chunks) - first_natural
        assert profile.counters()["flush_early_rows"] == n_early * CHUNK_ROWS
        by_trigger = {}
        for trig, _live, shipped in takes():
            by_trigger.setdefault(trig, set()).add(shipped)
        # an early launch ships the natural launch's rectangle
        assert by_trigger["early"] == by_trigger["natural"]
        assert set(by_trigger) == {"natural", "early", "eos"}


# ------------------------------------------- (b) natural launches: never

def test_chunks_of_flush_rows_never_flush_early():
    chunks = cb_chunks(12, chunk_ids=FLUSH_ROWS // N_KEYS, seed=5)
    got = feed(make_core(), chunks)
    assert_same(got, oracle(CB, chunks))
    assert early() == 0
    assert {t for t, _l, _s in takes()} <= {"natural", "eos"}


# ------------------------------ (c) time-based: only right after a close

def test_tb_stream_flushes_early_only_after_a_close():
    spec = WindowSpec(200, 200, WinType.TB)
    chunks = tb_chunks(45)               # 40 rows a chunk, a close every 20
    seen = []
    got = feed(make_core(spec, flush_rows=8 * 40), chunks,
               after_chunk=lambda i: seen.append(early()))
    assert_same(got, oracle(spec, chunks))
    # chunks 0-19 lie in the first window: rows pend, natural launches are
    # made (chunks 7 and 15), the ring is idle — and no window has fired
    assert seen[19] == 0
    # chunk 20 brings ts 200: every key's window closes in that call
    assert seen[20] == 1
    # and nothing more until the next close, at chunk 40
    assert seen[39] == 1
    assert seen[40] == 2


# ------------------- (d) recovery mode and max_delay_ms: launches as before

def _launch_sizes(run):
    profile.reset()
    run()
    assert early() == 0
    cuts = takes()
    assert "early" not in {t for t, _l, _s in cuts}
    return cuts


def test_recovery_mode_makes_the_same_launches():
    chunks = cb_chunks(24, seed=7)
    want = oracle(CB, chunks)

    def run():
        core = make_core()
        outs = []
        for b in chunks:
            outs.extend(core.process_batches(b))
            settle(core)
        outs.extend(core.flush_batches())
        assert_same(np.sort(np.concatenate(outs), order=["key", "id"]), want)
        return [len(o) for o in outs]

    sizes = []
    cuts = [_launch_sizes(lambda: sizes.append(run())) for _ in range(2)]
    assert cuts[0] == cuts[1]
    assert sizes[0] == sizes[1]
    # one natural launch every 8th chunk and the end-of-stream flush
    assert [t for t, _l, _s in cuts[0]] == ["natural"] * 3 + ["eos"]


def test_max_delay_keeps_its_timer():
    chunks = cb_chunks(24, seed=9)
    want = oracle(CB, chunks)

    def run():
        # a budget no run reaches: the timer never fires, and the
        # device-following flush stays out of its way
        got = feed(make_core(max_delay_ms=600_000.0), chunks)
        assert_same(got, want)

    cuts = [_launch_sizes(run) for _ in range(2)]
    assert cuts[0] == cuts[1]
    assert [t for t, _l, _s in cuts[0]] == ["natural"] * 3 + ["eos"]


def test_expired_max_delay_is_forced_not_early():
    chunks = cb_chunks(6, seed=11)
    core = make_core(max_delay_ms=1.0)
    outs = []
    for b in chunks:
        outs.append(core.process(b))
        time.sleep(0.003)
    outs.append(core.flush())
    core._stop_worker()
    assert_same(np.sort(np.concatenate(outs), order=["key", "id"]),
                oracle(CB, chunks))
    assert early() == 0
    assert "forced" in {t for t, _l, _s in takes()}


# --------------------------------------- (e) the ring's state and its cost

def test_a_busy_ring_is_left_alone():
    chunks = cb_chunks(24, seed=13)
    core = make_core()
    # the device never done with what it was sent (condition 3)
    core.executor.ring_idle = lambda: False
    got = feed(core, chunks, idle_between=False)
    assert_same(got, oracle(CB, chunks))
    assert early() == 0


def test_cost_guard_keeps_early_flushes_to_their_share():
    """The guard reads the service the executor measures, which a ship
    thread that waits on its launches keeps honest: a ring whose results
    take 30 ms gets an early flush per 60 ms, however fast the chunks come."""
    fetch_s = 0.03
    chunks = cb_chunks(72, seed=17)
    want = oracle(CB, chunks)
    # the step shapes compiled beforehand: a compile is no launch service
    assert_same(feed(make_core(), chunks), want)
    profile.reset()
    core = slow_device(make_core(service_s=None), fetch_s)
    t0 = []

    def pace(i):
        if i == FLUSH_ROWS // CHUNK_ROWS - 1:
            t0.append(time.monotonic())     # from here on it may engage
        time.sleep(0.004)

    got = feed(core, chunks, after_chunk=pace)
    elapsed = time.monotonic() - t0[0]
    assert_same(got, want)
    assert core.executor.mean_service_s() >= fetch_s
    n_early = early()
    # every chunk would have flushed (the first test); the guard lets one
    # through per service / share of wall time, and no more
    assert 0 < n_early <= elapsed * native_core._EARLY_SHARE / fetch_s + 1
    assert n_early < len(chunks) // 2
