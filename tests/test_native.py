"""Differential tests for the C++ native window core (native/wf_native.cpp
via NativeResidentCore): byte-identical results to the pure-Python host core
on the same streams — the native twin of test_resident.py.  Skipped when the
native toolchain is unavailable."""

import warnings

import numpy as np
import pytest

from windflow_tpu.core.tuples import Schema, batch_from_columns
from windflow_tpu.core.windows import PatternConfig, Role, WindowSpec, WinType
from windflow_tpu.core.winseq import WinSeqCore
from windflow_tpu.ops.functions import Reducer

native = pytest.importorskip("windflow_tpu.native")
if not native.available():
    pytest.skip("native library unavailable", allow_module_level=True)

from windflow_tpu.patterns.native_core import NativeResidentCore  # noqa: E402
from windflow_tpu.patterns.win_seq_tpu import make_core_for  # noqa: E402

SCHEMA = Schema(value=np.int64)


def make_native(spec, reducer, **kw):
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        return NativeResidentCore(spec, reducer, **kw)


def run_core(core, batches):
    outs = []
    for b in batches:
        r = core.process(b)
        if len(r):
            outs.append(r)
    r = core.flush()
    if len(r):
        outs.append(r)
    if not outs:
        return np.zeros(0, dtype=core._result_dtype)
    return np.sort(np.concatenate(outs), order=["key", "id"])


def cb_stream(n_keys, per_key, chunk=37, seed=0, lo_val=-50, hi_val=100):
    rng = np.random.default_rng(seed)
    batches = []
    for lo in range(0, per_key, chunk):
        m = min(chunk, per_key - lo)
        ids = np.repeat(np.arange(lo, lo + m), n_keys)
        keys = np.tile(np.arange(n_keys), m)
        vals = rng.integers(lo_val, hi_val, size=m * n_keys).astype(np.int64)
        batches.append(batch_from_columns(
            SCHEMA, key=keys, id=ids, ts=ids, value=vals))
    return batches


def assert_equal_results(a, b):
    assert len(a) == len(b)
    for f in ("key", "id", "ts", "value"):
        np.testing.assert_array_equal(a[f], b[f])


def test_native_is_default_selection(monkeypatch):
    monkeypatch.delenv("WF_NO_NATIVE", raising=False)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        core = make_core_for(WindowSpec(16, 4, WinType.CB), Reducer("sum"))
    assert isinstance(core, NativeResidentCore)


@pytest.mark.parametrize("op", ["sum", "min", "max", "prod"])
@pytest.mark.parametrize("win,slide", [(16, 4), (8, 8), (4, 12)])
@pytest.mark.parametrize("n_keys", [1, 5])
def test_native_cb_matches_host(op, win, slide, n_keys):
    lo, hi = (1, 3) if op == "prod" else (-50, 100)
    batches = cb_stream(n_keys, 503, seed=win * 31 + slide,
                        lo_val=lo, hi_val=hi)
    spec = WindowSpec(win, slide, WinType.CB)
    host = run_core(WinSeqCore(spec, Reducer(op)), batches)
    nat = make_native(spec, Reducer(op), batch_len=64, flush_rows=200)
    assert_equal_results(host, run_core(nat, batches))


@pytest.mark.parametrize("op", ["sum", "max"])
@pytest.mark.parametrize("win,slide", [(20, 5), (10, 10), (6, 16)])
def test_native_tb_matches_host(op, win, slide):
    rng = np.random.default_rng(win + slide)
    nk, per = 3, 400
    ts_all = np.sort(rng.integers(0, 900, size=per))
    batches = []
    for lo in range(0, per, 53):
        m = min(53, per - lo)
        batches.append(batch_from_columns(
            SCHEMA, key=np.tile(np.arange(nk), m),
            id=np.repeat(np.arange(lo, lo + m), nk),
            ts=np.repeat(ts_all[lo:lo + m], nk),
            value=rng.integers(0, 100, size=m * nk).astype(np.int64)))
    spec = WindowSpec(win, slide, WinType.TB)
    host = run_core(WinSeqCore(spec, Reducer(op)), batches)
    nat = make_native(spec, Reducer(op), batch_len=32, flush_rows=150)
    assert_equal_results(host, run_core(nat, batches))


@pytest.mark.parametrize("role,cfg", [
    (Role.PLQ, PatternConfig(0, 1, 8, 1, 2, 8)),
    (Role.MAP, PatternConfig(0, 1, 8, 0, 1, 8)),
    (Role.WLQ, PatternConfig(1, 2, 8, 0, 1, 8)),
])
def test_native_role_renumbering(role, cfg):
    batches = cb_stream(3, 300, chunk=29, seed=7)
    spec = WindowSpec(8, 8, WinType.CB)
    host = run_core(
        WinSeqCore(spec, Reducer("sum"), config=cfg, role=role,
                   map_indexes=(1, 3)), batches)
    nat = make_native(spec, Reducer("sum"), config=cfg, role=role,
                      map_indexes=(1, 3), batch_len=32, flush_rows=100)
    assert_equal_results(host, run_core(nat, batches))


def test_native_regular_descriptors_engage():
    """Steady-state CB sliding windows must take the compressed
    regular-descriptor launch path (per-key scalars expanded on device),
    and still match the host core."""
    from windflow_tpu.ops.resident import ResidentWindowExecutor
    batches = cb_stream(4, 800, chunk=100, seed=31)
    spec = WindowSpec(16, 4, WinType.CB)
    want = run_core(WinSeqCore(spec, Reducer("sum")), batches)
    # count actual launch_regular dispatches (a cache-key delta is order-
    # dependent: the prewarm ladder in an earlier test may have compiled
    # this shape already)
    calls = []
    orig = ResidentWindowExecutor.launch_regular

    def counting(self, *a, **kw):
        calls.append(1)
        return orig(self, *a, **kw)

    ResidentWindowExecutor.launch_regular = counting
    try:
        core = make_native(spec, Reducer("sum"), batch_len=64,
                           flush_rows=250)
        assert_equal_results(want, run_core(core, batches))
    finally:
        ResidentWindowExecutor.launch_regular = orig
    assert calls, "regular-descriptor path never engaged"


def test_native_out_of_order_drops():
    """Late rows are dropped identically (win_seq.hpp:293-305)."""
    rng = np.random.default_rng(13)
    ids = np.arange(200)
    ids[50] = 10       # a late row mid-stream
    ids[120] = 100
    vals = rng.integers(0, 50, size=200).astype(np.int64)
    b = batch_from_columns(SCHEMA, key=np.zeros(200), id=ids, ts=ids,
                           value=vals)
    spec = WindowSpec(12, 4, WinType.CB)
    host = run_core(WinSeqCore(spec, Reducer("sum")), [b])
    nat = make_native(spec, Reducer("sum"), batch_len=16, flush_rows=64)
    assert_equal_results(host, run_core(nat, [b]))


def test_native_markers_and_empty_flush():
    """EOS markers advance firing without being archived."""
    from windflow_tpu.core.tuples import MARKER_FIELD
    b = batch_from_columns(SCHEMA, key=np.zeros(20), id=np.arange(20),
                           ts=np.arange(20) * 10,
                           value=np.ones(20, dtype=np.int64))
    m = batch_from_columns(SCHEMA, key=np.zeros(1), id=[40], ts=[400],
                           value=[0])
    m[MARKER_FIELD] = True
    spec = WindowSpec(8, 4, WinType.CB)
    host = run_core(WinSeqCore(spec, Reducer("sum")), [b, m])
    nat = make_native(spec, Reducer("sum"), batch_len=8, flush_rows=32)
    assert_equal_results(host, run_core(nat, [b, m]))


def test_native_falls_back_on_float_payload():
    schema = Schema(value=np.float64)
    b = batch_from_columns(schema, key=np.zeros(10), id=np.arange(10),
                           ts=np.arange(10),
                           value=np.arange(10, dtype=np.float64))
    nat = make_native(WindowSpec(4, 2, WinType.CB), Reducer("max"),
                      batch_len=8, flush_rows=32)
    out = np.concatenate([nat.process(b), nat.flush()])
    host_core = WinSeqCore(WindowSpec(4, 2, WinType.CB), Reducer("max"))
    want = np.concatenate([host_core.process(b), host_core.flush()])
    np.testing.assert_array_equal(np.sort(out, order=["key", "id"])["value"],
                                  np.sort(want, order=["key", "id"])["value"])


def test_native_wide_values_use_int32_wire():
    batches = cb_stream(2, 256, seed=5, lo_val=-40000, hi_val=40000)
    spec = WindowSpec(16, 4, WinType.CB)
    host = run_core(WinSeqCore(spec, Reducer("sum")), batches)
    nat = make_native(spec, Reducer("sum"), batch_len=64, flush_rows=300)
    assert_equal_results(host, run_core(nat, batches))


@pytest.mark.parametrize("overlap", [True, False])
@pytest.mark.parametrize("shards", [1, 3])
def test_native_overlap_and_shards_match_host(overlap, shards):
    """The ship-thread overlap mode and the synchronous mode produce
    identical results for any shard count."""
    batches = cb_stream(5, 400, chunk=41, seed=29)
    spec = WindowSpec(12, 4, WinType.CB)
    want = run_core(WinSeqCore(spec, Reducer("sum")), batches)
    core = make_native(spec, Reducer("sum"), batch_len=32, flush_rows=120,
                       shards=shards, overlap=overlap)
    assert_equal_results(want, run_core(core, batches))


def test_native_sharded_cores_concurrent_threads():
    """Two sharded cores driven from two threads concurrently (two windowed
    nodes in one pipeline): the shard pool must not mix their tasks —
    regression for the unserialized ShardPool::run data race."""
    import threading
    batches = cb_stream(6, 600, chunk=50, seed=23)
    spec = WindowSpec(16, 4, WinType.CB)
    want = run_core(WinSeqCore(spec, Reducer("sum")), batches)

    results = [None, None]
    def drive(i):
        core = make_native(spec, Reducer("sum"), batch_len=32,
                           flush_rows=120, shards=2)
        results[i] = run_core(core, batches)

    for _ in range(5):
        ts = [threading.Thread(target=drive, args=(i,)) for i in range(2)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        for r in results:
            assert_equal_results(want, r)


def test_native_hopping_gaps():
    batches = cb_stream(2, 300, chunk=41, seed=21)
    spec = WindowSpec(4, 10, WinType.CB)   # hopping: slide > win
    host = run_core(WinSeqCore(spec, Reducer("sum")), batches)
    nat = make_native(spec, Reducer("sum"), batch_len=16, flush_rows=100)
    assert_equal_results(host, run_core(nat, batches))


def test_native_max_delay_flushes_partial_batches():
    """Native core: max_delay_ms ships pending windows via
    wf_core_force_flush on the next process() after the deadline."""
    import time as _time
    import warnings
    import numpy as np
    from windflow_tpu.core.windows import WindowSpec, WinType
    from windflow_tpu.ops.functions import Reducer
    from windflow_tpu.patterns.native_core import NativeResidentCore
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        core = NativeResidentCore(WindowSpec(4, 4, WinType.CB),
                                  Reducer("sum"), batch_len=1 << 20,
                                  flush_rows=1 << 20, max_delay_ms=1)
    from windflow_tpu.core.tuples import Schema, batch_from_columns
    b = batch_from_columns(Schema(value=np.int64), key=np.zeros(8),
                           id=np.arange(8), ts=np.arange(8),
                           value=np.arange(8))
    got = core.process(b)
    _time.sleep(0.01)
    deadline = _time.monotonic() + 5
    n = len(got)
    while n == 0 and _time.monotonic() < deadline:
        _time.sleep(0.01)
        n += len(core.process(b[:0]))
    assert n > 0, "native max_delay did not ship the pending windows"
    core.flush()


def test_native_launch_coalescing_matches_host():
    """Adaptive launch coalescing (wf_launch_coalesce): many small queued
    launches fuse into fewer dispatches; results stay byte-identical to the
    host core.  Tiny flush_rows + big chunks force multiple launches per
    process() call, so the queue is >1 deep at every ship."""
    batches = cb_stream(5, 2000, chunk=997, seed=9)
    spec = WindowSpec(16, 4, WinType.CB)
    host = run_core(WinSeqCore(spec, Reducer("sum")), batches)
    nat = make_native(spec, Reducer("sum"), batch_len=1 << 20,
                      flush_rows=64, overlap=False)
    # count actual merges through the C ABI (not just queue depth: a
    # regressed try_merge that always refuses would still keep results
    # correct via unmerged dispatches)
    merges = []
    real = nat._lib

    class _Shim:
        def __getattr__(self, name):
            if name != "wf_launch_coalesce":
                return getattr(real, name)

            def counting(h, cells, mx, mult):
                n = real.wf_launch_coalesce(h, cells, mx, mult)
                merges.append(n)
                return n
            return counting

    nat._lib = _Shim()
    got = run_core(nat, batches)
    assert_equal_results(host, got)
    assert sum(merges) > 0, "wf_launch_coalesce never merged a pair"


def test_native_coalesce_across_value_widths():
    """Launches whose wire dtypes differ (int8 vs int16 chunks) widen on
    merge without corrupting values."""
    spec = WindowSpec(8, 8, WinType.CB)
    rng = np.random.default_rng(3)
    batches = []
    for c, (lo, hi) in enumerate([(-5, 5), (-3000, 3000), (-5, 5),
                                  (-30000, 30000)]):
        m = 256
        ids = np.repeat(np.arange(c * m, (c + 1) * m), 3)
        keys = np.tile(np.arange(3), m)
        vals = rng.integers(lo, hi, size=m * 3).astype(np.int64)
        batches.append(batch_from_columns(
            SCHEMA, key=keys, id=ids, ts=ids, value=vals))
    host = run_core(WinSeqCore(spec, Reducer("sum")), batches)
    nat = make_native(spec, Reducer("sum"), batch_len=1 << 20,
                      flush_rows=96, overlap=False)
    assert_equal_results(host, run_core(nat, batches))


def test_native_periodic_fast_path_equals_general():
    """The periodic-chunk bulk path must be row-identical to the general
    loop: the same logical stream arranged periodically (fast path
    engages) and pair-shuffled (detection bails -> general loop) gives
    identical sorted results."""
    spec = WindowSpec(16, 4, WinType.CB)
    n_keys, per_key = 8, 600
    rng = np.random.default_rng(31)
    vals = rng.integers(-50, 100, size=per_key * n_keys).astype(np.int64)

    def stream(shuffled):
        batches = []
        for lo in range(0, per_key, 97):
            m = min(97, per_key - lo)
            keys = np.tile(np.arange(n_keys), m)
            ids = np.repeat(np.arange(lo, lo + m), n_keys)
            v = vals[lo * n_keys:(lo + m) * n_keys]
            if shuffled:
                # swap adjacent different-key rows: periodicity breaks,
                # per-key order survives
                perm = np.arange(m * n_keys)
                even = perm[: (m * n_keys) // 2 * 2]
                perm[: len(even)] = even.reshape(-1, 2)[:, ::-1].ravel()
                keys, ids, v = keys[perm], ids[perm], v[perm]
            batches.append(batch_from_columns(
                SCHEMA, key=keys, id=ids, ts=ids * 7, value=v))
        return batches

    a = run_core(make_native(spec, Reducer("sum"), batch_len=64,
                             flush_rows=500), stream(False))
    b = run_core(make_native(spec, Reducer("sum"), batch_len=64,
                             flush_rows=500), stream(True))
    host = run_core(WinSeqCore(spec, Reducer("sum")), stream(False))
    assert_equal_results(host, a)
    assert_equal_results(host, b)


def test_native_periodic_fast_path_cross_chunk_gap():
    """A periodic chunk whose per-key ids jump past the previous chunk's
    (id gap across chunks) must produce the same empty-window firings as
    the general loop."""
    spec = WindowSpec(8, 8, WinType.CB)
    n_keys = 4

    def chunk(lo, m):
        return batch_from_columns(
            SCHEMA, key=np.tile(np.arange(n_keys), m),
            id=np.repeat(np.arange(lo, lo + m), n_keys),
            ts=np.repeat(np.arange(lo, lo + m), n_keys),
            value=np.arange(m * n_keys, dtype=np.int64))

    batches = [chunk(0, 20), chunk(50, 20), chunk(200, 20)]
    host = run_core(WinSeqCore(spec, Reducer("sum")), batches)
    nat = make_native(spec, Reducer("sum"), batch_len=32, flush_rows=64)
    assert_equal_results(host, run_core(nat, batches))


def _ts_of(keys, ids):
    """A row's own timestamp, apart from its id and from any other key's."""
    return np.asarray(ids) * 7 + np.asarray(keys) * 3 + 11


def _periodic(n_keys, lo, m, rng):
    keys = np.tile(np.arange(n_keys), m)
    ids = np.repeat(np.arange(lo, lo + m), n_keys)
    return batch_from_columns(
        SCHEMA, key=keys, id=ids, ts=_ts_of(keys, ids),
        value=rng.integers(-50, 100, size=m * n_keys).astype(np.int64))


def _swap_pair(b, at):
    """Two neighbours of different keys change places: the key period
    breaks at row `at`, each key's own order survives."""
    b = b.copy()
    b[[at, at + 1]] = b[[at + 1, at]]
    return b


def _ts_case(case):
    """(spec, core arguments, batches, how much of the stream the bulk path
    of a one-shard core must take: "all", "some" or "none")."""
    from windflow_tpu.core.tuples import MARKER_FIELD
    rng = np.random.default_rng(len(case))
    if case in ("bulk_blocks", "bulk_break"):
        # 6 keys, blocks of 32 rows: a block ends inside a key period and
        # inside a chunk, a chunk ends inside a block
        bs = [_periodic(6, lo, min(97, 600 - lo), rng)
              for lo in range(0, 600, 97)]
        if case == "bulk_break":
            bs[2] = _swap_pair(bs[2], 301)
        return (WindowSpec(16, 4, WinType.CB),
                dict(batch_len=8, flush_rows=500), bs,
                "all" if case == "bulk_blocks" else "some")
    if case in ("bulk_gaps", "general_gaps"):
        # ids jump between chunks: one row closes several windows, the
        # first of them on the chunk before's last row, the rest empty
        bs = [_periodic(4, lo, 21, rng) for lo in (0, 50, 200)]
        if case == "general_gaps":
            bs = [_swap_pair(b, 0) for b in bs]
        return (WindowSpec(8, 4, WinType.CB),
                dict(batch_len=32, flush_rows=64), bs,
                "all" if case == "bulk_gaps" else "none")
    assert case == "markers_eos"
    # a marker row a key closes windows on the newest archived row (it is
    # archived itself nowhere); more rows; windows left open at the end
    mk = batch_from_columns(SCHEMA, key=np.arange(3), id=[30, 31, 37],
                            ts=[4000, 4001, 4002], value=np.zeros(3))
    mk[MARKER_FIELD] = True
    bs = [_periodic(3, 0, 21, rng), mk, _periodic(3, 48, 23, rng)]
    return (WindowSpec(8, 4, WinType.CB),
            dict(batch_len=16, flush_rows=48), bs, "all")


@pytest.mark.parametrize("shards", [1, 2])
@pytest.mark.parametrize("case", ["bulk_blocks", "bulk_break", "bulk_gaps",
                                  "general_gaps", "markers_eos"])
def test_native_cb_result_ts_is_the_host_cores(case, shards):
    """A count-based window's result ts is its last row's, and the native
    archive keeps no ts column to read it from: the bulk path reads it in
    the input chunk (or carries it over from the block before), the general
    loop carries the newest archived row's.  Bit-identical to the host core
    on every path, with a ts that is no function of the id alone."""
    spec, kw, batches, bulk = _ts_case(case)
    host = run_core(WinSeqCore(spec, Reducer("sum")), batches)
    assert len(np.unique(host["ts"])) > 12
    nat = make_native(spec, Reducer("sum"), shards=shards, **kw)
    assert_equal_results(host, run_core(nat, batches))
    rows = sum(int((~b["marker"]).sum()) for b in batches)
    if shards > 1 or bulk == "none":
        assert nat.fast_rows == 0
    elif bulk == "all":
        assert nat.fast_rows == rows
    else:
        assert 0 < nat.fast_rows < rows


@pytest.mark.parametrize("kind,fields,want", [
    ("cb_sum", 1, 16), ("tb_sum", 1, 16), ("tb_multi", 2, 24),
    ("cb_multi", 3, 32)])
def test_archive_row_bytes_counts_the_columns(kind, fields, want):
    """8 bytes a column: the position and each shipped field -- no ts (the
    arg-extremum cores, which carry columns and on count-based windows keep
    ts, are tests/test_argext.py's)."""
    from windflow_tpu.ops.functions import MultiReducer
    sch = Schema(a=np.int64, b=np.int64, c=np.int64)
    wt = WinType.CB if kind.startswith("cb") else WinType.TB
    fn = (Reducer("sum", "a") if fields == 1 else MultiReducer(*[
        Reducer("sum", f, "s" + f, value_range=(0, 100))
        for f in "abc"[:fields]]))
    core = make_native(WindowSpec(8, 4, wt), fn)
    assert core.archive_row_bytes == want
    n = 40
    b = batch_from_columns(sch, key=np.zeros(n), id=np.arange(n),
                           ts=np.arange(n) * 2, a=np.arange(n) % 7,
                           b=np.arange(n) % 5, c=np.arange(n) % 3)
    host = WinSeqCore(WindowSpec(8, 4, wt), fn)
    want_rows = np.concatenate([host.process(b), host.flush()])
    got = np.concatenate([core.process(b), core.flush()])
    assert np.array_equal(np.sort(got, order="id"),
                          np.sort(want_rows, order="id"))


@pytest.mark.parametrize("periodic", [True, False],
                         ids=["periodic", "shuffled"])
def test_the_window_workers_log_says_what_its_archive_took(periodic,
                                                           tmp_path):
    """``archive_row_bytes`` and ``fast_rows`` in the window worker's node
    log: 16 bytes a row for a single-field sum, and every row of a
    key-periodic stream through the bulk path (none of a shuffled one)."""
    import json
    from windflow_tpu.api import MultiPipe
    from windflow_tpu.patterns.basic import Sink, Source
    from windflow_tpu.patterns.win_seq_tpu import WinSeqTPU
    rng = np.random.default_rng(2)
    chunks = [_periodic(8, lo, 64, rng) for lo in range(0, 512, 64)]
    if not periodic:
        chunks = [_swap_pair(c, 0) for c in chunks]
    got = []

    def source(shipper):
        for c in chunks:
            shipper.push_batch(c.copy())

    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        pipe = (MultiPipe("job", trace_dir=str(tmp_path))
                .add_source(Source(source, SCHEMA, name="src"))
                .add(WinSeqTPU(Reducer("sum"), 16, 4, WinType.CB,
                               batch_len=64, flush_rows=256, name="win"))
                .add_sink(Sink(lambda r: got.append(r.copy())
                               if r is not None else None, vectorized=True)))
        pipe.run_and_wait_end(timeout=120)
    host = run_core(WinSeqCore(WindowSpec(16, 4, WinType.CB),
                               Reducer("sum")), chunks)
    assert_equal_results(host, np.sort(np.concatenate(got),
                                       order=["key", "id"]))
    logs = [json.loads(p.read_text()) for p in tmp_path.glob("*.log")]
    (win,) = [n for n in logs if "archive_row_bytes" in n]
    assert "win" in win["node"] and win["archive_row_bytes"] == 16
    assert win["fast_rows"] == (win["rcv_tuples"] if periodic else 0)
    assert win["rcv_tuples"] == 8 * 512


def test_native_rebase_reships_wide_values_on_wide_wire():
    """A ring rebase re-ships ALL live rows; the wire dtype must cover the
    re-shipped (previously shipped) values, not just the pending ones —
    narrow-wire truncation here silently corrupts aggregates."""
    spec = WindowSpec(16, 4, WinType.CB)
    # key 0 ships 8 rows of 3000 (int16 wire) first ...
    b1 = batch_from_columns(SCHEMA, key=np.zeros(8), id=np.arange(8),
                            ts=np.arange(8),
                            value=np.full(8, 3000, dtype=np.int64))
    # ... then 19 NEW keys with tiny values force KP growth -> rebase;
    # the rebase launch re-ships key 0's live 3000s
    rows = []
    for i in range(8, 20):
        for k in range(20):
            rows.append((k, i))
    keys = np.array([r[0] for r in rows])
    ids = np.array([r[1] for r in rows])
    b2 = batch_from_columns(SCHEMA, key=keys, id=ids, ts=ids,
                            value=np.ones(len(rows), dtype=np.int64))
    host = run_core(WinSeqCore(spec, Reducer("sum")), [b1, b2])
    nat = make_native(spec, Reducer("sum"), batch_len=1 << 20, flush_rows=8)
    assert_equal_results(host, run_core(nat, [b1, b2]))


def test_ship_thread_failure_surfaces_and_salvages():
    """A one-shot executor failure on the ship thread must surface on the
    node thread's next process()/flush(); a caller that catches it and
    keeps streaming gets the already-harvested results back (salvage
    path, native_core.py:_raise_ship_exc) and the stream completes."""
    spec = WindowSpec(8, 4, WinType.CB)
    nat = make_native(spec, Reducer("sum"), batch_len=8, flush_rows=32,
                      overlap=True)
    boom = {"at": 3, "calls": 0}
    ex = nat.executors[0]
    orig_launch = ex.launch
    orig_reg = ex.launch_regular

    def failing(*a, **kw):
        boom["calls"] += 1
        if boom["calls"] == boom["at"]:     # fail exactly once
            raise RuntimeError("injected wire failure")
        # launch() takes 6 positional args, launch_regular 9+
        return (orig_reg if len(a) > 6 else orig_launch)(*a, **kw)

    ex.launch = failing
    ex.launch_regular = failing
    batches = cb_stream(2, 400, chunk=50, seed=21)
    rows_before = rows_after = 0
    raised = False
    for b in batches:
        try:
            n = len(nat.process(b))
        except RuntimeError as e:
            assert "injected" in str(e)
            raised = True
            continue
        if raised:
            rows_after += n
        else:
            rows_before += n
    try:
        rows_after += len(nat.flush())
    except RuntimeError as e:
        # failure surfaced at drain time: it raises exactly once, and the
        # retry returns everything salvaged plus the remaining windows
        assert "injected" in str(e)
        raised = True
        rows_after += len(nat.flush())
    assert raised, "injected failure never surfaced"
    # the stream kept going after the caught failure and produced the
    # remaining windows (incl. any salvaged across the raise); only the
    # single failed launch's windows may be missing
    assert rows_after > 0


def test_ship_thread_failure_cancels_dataflow(monkeypatch):
    """A device failure inside a windowed node must cancel the whole
    graph (no deadlock), like any node exception (runtime/engine.py)."""
    from windflow_tpu.core.tuples import Schema
    from windflow_tpu.ops.resident import ResidentWindowExecutor
    from windflow_tpu.patterns.basic import Sink, Source
    from windflow_tpu.patterns.win_seq_tpu import WinSeqTPU
    from windflow_tpu.runtime.engine import Dataflow
    from windflow_tpu.runtime.farm import build_pipeline

    def boom(self, *a, **kw):
        raise RuntimeError("injected device failure")

    monkeypatch.setattr(ResidentWindowExecutor, "launch", boom)
    monkeypatch.setattr(ResidentWindowExecutor, "launch_regular", boom)
    schema = Schema(value=np.int64)
    df = Dataflow()
    build_pipeline(df, [Source(batches=iter(cb_stream(2, 300, chunk=40)),
                               schema=schema),
                        WinSeqTPU(Reducer("sum"), 8, 4, WinType.CB,
                                  batch_len=8, flush_rows=32),
                        Sink(lambda r: None, vectorized=True)])
    with pytest.raises(RuntimeError, match="injected"):
        df.run_and_wait_end()


def test_native_deep_coalescing_ladder():
    """With the wire reported slow (mean service >= 50 ms), the buddy
    ladder is allowed up to 16x: a stream producing hundreds of regular
    launches must reach dispatch counts well below the 4x cap's floor,
    with results still byte-identical to the host core."""
    spec = WindowSpec(16, 4, WinType.CB)
    batches = cb_stream(4, 20000, chunk=2048, seed=5)
    host = run_core(WinSeqCore(spec, Reducer("sum")), batches)
    nat = make_native(spec, Reducer("sum"), batch_len=1 << 20,
                      flush_rows=256, overlap=False)
    # ~312 natural launches (4*20000/256); pretend the wire is stalled so
    # the adaptive cap opens the full ladder
    for ex in nat.executors:
        ex.mean_service_s = lambda: 1.0
    dispatches = []
    for ex in nat.executors:
        orig_r, orig_i = ex.launch_regular, ex.launch

        def count_r(*a, _f=orig_r, **kw):
            dispatches.append("r")
            return _f(*a, **kw)

        def count_i(*a, _f=orig_i, **kw):
            dispatches.append("i")
            return _f(*a, **kw)
        ex.launch_regular, ex.launch = count_r, count_i
    got = run_core(nat, batches)
    assert_equal_results(host, got)
    n_launch = 4 * 20000 // 256
    # the 4x-capped ladder could at best reach ~n_launch/4 (plus rebases);
    # the 16x ladder must do strictly better than that floor
    assert len(dispatches) < n_launch // 4, (
        f"{len(dispatches)} dispatches for ~{n_launch} launches — deep "
        "coalescing did not engage")


def test_native_rebase_launches_never_merge():
    """ADVICE r2: try_merge must reject a rebase launch in either role (A
    or B) — a rebase is a dispatch barrier.  Queue exactly [rebase,
    regular] and coalesce: nothing may merge."""
    spec = WindowSpec(8, 4, WinType.CB)
    # flush_rows far above the feeds: each force_flush makes exactly one
    # launch, so the queue is exactly [rebase, regular]
    nat = make_native(spec, Reducer("sum"), batch_len=1 << 20,
                      flush_rows=4096, overlap=False)
    lib, h = nat._lib, nat._hs[0]
    b1 = cb_stream(2, 32, chunk=32, seed=1)[0]
    off = nat._field_offsets(b1)
    itemsize, o_key, o_id, o_ts, o_mk, o_val = off

    def feed(b):
        bb = np.ascontiguousarray(b)
        lib.wf_cores_process_mt(nat._harr, 1, bb.ctypes.data, len(bb),
                                itemsize, o_key, o_id, o_ts, o_mk, o_val)

    # first flush = rebase launch; second = regular continuation
    feed(cb_stream(2, 64, chunk=64, seed=1)[0])
    lib.wf_core_force_flush(h)
    feed(batch_from_columns(SCHEMA, key=np.tile(np.arange(2), 64),
                            id=np.repeat(np.arange(64, 128), 2),
                            ts=np.repeat(np.arange(64, 128), 2),
                            value=np.ones(128, dtype=np.int64)))
    lib.wf_core_force_flush(h)
    assert lib.wf_launch_pending(h) == 2
    merged = lib.wf_launch_coalesce(h, 1 << 24, 16, 16)
    assert merged == 0, "a rebase launch was merged"
    assert lib.wf_launch_pending(h) == 2
    # drain normally so results stay correct
    host = run_core(WinSeqCore(spec, Reducer("sum")),
                    [cb_stream(2, 64, chunk=64, seed=1)[0],
                     batch_from_columns(
                         SCHEMA, key=np.tile(np.arange(2), 64),
                         id=np.repeat(np.arange(64, 128), 2),
                         ts=np.repeat(np.arange(64, 128), 2),
                         value=np.ones(128, dtype=np.int64))])
    got = run_core(nat, [])
    assert_equal_results(host, got)


def test_prewarm_regular_ladder_covers_merged_shapes():
    """After a run that compiled base regular buckets, the ladder prewarm
    must add the {2x..16x} siblings the coalescer can produce (ring-
    capped), so a wire-stalled timed run never compiles mid-flight."""
    from windflow_tpu.ops import resident as R
    spec = WindowSpec(16, 4, WinType.CB)
    batches = cb_stream(4, 4000, chunk=2048, seed=11)
    nat = make_native(spec, Reducer("sum"), batch_len=1 << 20,
                      flush_rows=256, overlap=False)
    run_core(nat, batches)
    base = [k for k in R._STEP_CACHE if k.family == "regular"]
    assert base, "no regular buckets compiled"
    n = R.prewarm_regular_ladder()
    assert n > 0
    for key in base:
        for m in (2, 4, 8, 16):
            if (key.Rb * m > key.cap
                    or (key.KP // 2 + 1) * key.Rb * m > (1 << 24)):
                continue
            sk = key._replace(Rb=key.Rb * m, Bb=key.Bb * m)
            assert sk in R._STEP_CACHE, f"ladder sibling missing: {sk}"
    # idempotent: a second call has nothing left to do
    assert R.prewarm_regular_ladder() == 0


def test_native_multistat_pos_max_split():
    """r3: a MultiReducer with one device-worthy stat rides the native
    core — counts from window lengths, MAX(position) from the C++
    archive's per-window last row (hpmax), sum shipped — and matches the
    host core field-for-field on both TB and CB windows."""
    from windflow_tpu.ops.functions import MultiReducer

    def agg():
        return MultiReducer(("count", None, "n"), ("max", "ts", "hi"),
                            ("sum", "value", "sm"))

    # TB: position field is ts -> max(ts) is the pos-max part
    spec = WindowSpec(50, 50, WinType.TB)
    rng = np.random.default_rng(17)
    nk, per = 3, 400
    batches = []
    for lo in range(0, per, 61):
        m = min(61, per - lo)
        batches.append(batch_from_columns(
            SCHEMA, key=np.tile(np.arange(nk), m),
            id=np.repeat(np.arange(lo, lo + m), nk),
            ts=np.repeat(np.arange(lo, lo + m) * 7, nk),
            value=rng.integers(-50, 100, size=m * nk).astype(np.int64)))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        core = make_core_for(spec, agg(), batch_len=32, flush_rows=100)
    assert isinstance(core, NativeResidentCore)
    assert [p.out_field for p in core._pos_max_parts] == ["hi"]
    host = run_core(WinSeqCore(spec, agg()), batches)
    got = run_core(core, batches)
    assert len(host) == len(got)
    for f in ("key", "id", "ts", "n", "hi", "sm"):
        np.testing.assert_array_equal(host[f], got[f], err_msg=f)

    # CB sliding: regular-descriptor launches must carry hpmax too
    spec = WindowSpec(16, 4, WinType.CB)
    cb_agg = MultiReducer(("count", None, "n"), ("max", "id", "hi"),
                          ("sum", "value", "sm"))
    batches = cb_stream(4, 900, chunk=128, seed=23)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        core = make_core_for(spec, cb_agg, batch_len=1 << 20,
                             flush_rows=200)
    assert isinstance(core, NativeResidentCore)
    host = run_core(WinSeqCore(spec, MultiReducer(
        ("count", None, "n"), ("max", "id", "hi"),
        ("sum", "value", "sm"))), batches)
    got = run_core(core, batches)
    assert len(host) == len(got)
    for f in ("key", "id", "ts", "n", "hi", "sm"):
        np.testing.assert_array_equal(host[f], got[f], err_msg=f)


def test_native_irregular_coalescing_tb_matches_host():
    """TB launches carry explicit window descriptors; under a stalled
    wire they must merge on those descriptors (r3: coalescing is no
    longer regular-only) with results identical to the host core."""
    rng = np.random.default_rng(41)
    nk, per = 3, 6000
    ts_all = np.sort(rng.integers(0, 3000, size=per))
    batches = []
    for lo in range(0, per, 512):
        m = min(512, per - lo)
        batches.append(batch_from_columns(
            SCHEMA, key=np.tile(np.arange(nk), m),
            id=np.repeat(np.arange(lo, lo + m), nk),
            ts=np.repeat(ts_all[lo:lo + m], nk),
            value=rng.integers(0, 100, size=m * nk).astype(np.int64)))
    spec = WindowSpec(20, 5, WinType.TB)
    host = run_core(WinSeqCore(spec, Reducer("sum")), batches)
    nat = make_native(spec, Reducer("sum"), batch_len=1 << 20,
                      flush_rows=128, overlap=False)
    for ex in nat.executors:
        ex.mean_service_s = lambda: 1.0   # pretend stall: full ladder
    merges = []
    real = nat._lib

    class _Shim:
        def __getattr__(self, name):
            if name != "wf_launch_coalesce":
                return getattr(real, name)

            def counting(h, cells, mx, mult):
                n = real.wf_launch_coalesce(h, cells, mx, mult)
                merges.append(n)
                return n
            return counting

    nat._lib = _Shim()
    got = run_core(nat, batches)
    assert_equal_results(host, got)
    assert sum(merges) > 0, "TB (irregular) launches never merged"


# ------------------------------------------------- multi-field staging (r5)

MF_SCHEMA = Schema(rev=np.int64, amt=np.int64)


def mf_stream(n_keys, per_key, chunk=61, seed=0, amt_lo=-40000,
              amt_hi=40000):
    """Two int64 payload columns with different value ranges (rev fits
    int8, amt needs int16/int32) so per-field wire narrowing is live."""
    rng = np.random.default_rng(seed)
    batches = []
    for lo in range(0, per_key, chunk):
        m = min(chunk, per_key - lo)
        ids = np.repeat(np.arange(lo, lo + m), n_keys)
        keys = np.tile(np.arange(n_keys), m)
        batches.append(batch_from_columns(
            MF_SCHEMA, key=keys, id=ids, ts=ids,
            rev=rng.integers(0, 50, size=m * n_keys).astype(np.int64),
            amt=rng.integers(amt_lo, amt_hi,
                             size=m * n_keys).astype(np.int64)))
    return batches


def mf_agg():
    from windflow_tpu.ops.functions import MultiReducer
    return MultiReducer(("count", None, "n"), ("max", "id", "hi"),
                        ("sum", "rev", "rsum"), ("min", "amt", "alo"),
                        ("max", "amt", "ahi"))


def assert_mf_equal(host, got, fields=("key", "id", "ts", "n", "hi",
                                       "rsum", "alo", "ahi")):
    assert len(host) == len(got)
    for f in fields:
        np.testing.assert_array_equal(host[f], got[f], err_msg=f)


@pytest.mark.parametrize("win,slide,wt", [
    (16, 4, WinType.CB), (24, 24, WinType.CB), (50, 25, WinType.TB)])
def test_native_multifield_matches_host(win, slide, wt):
    """r5: a MultiReducer with >1 device-worthy stat over 2 fields stages
    per-field columns through the C++ core (wf_core_set_fields /
    wf_cores_process_mt_f) into per-field device rings and matches the
    host core field-for-field — counts and MAX(position) still answered
    host-side, the two payload columns narrowed independently."""
    spec = WindowSpec(win, slide, wt)
    if wt is WinType.TB:
        rng = np.random.default_rng(5)
        nk, per = 3, 420
        batches = []
        for lo in range(0, per, 71):
            m = min(71, per - lo)
            batches.append(batch_from_columns(
                MF_SCHEMA, key=np.tile(np.arange(nk), m),
                id=np.repeat(np.arange(lo, lo + m), nk),
                ts=np.repeat(np.arange(lo, lo + m) * 7, nk),
                rev=rng.integers(0, 50, size=m * nk).astype(np.int64),
                amt=rng.integers(-9000, 9000,
                                 size=m * nk).astype(np.int64)))
    else:
        batches = mf_stream(4, 700, seed=win + slide)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        core = make_core_for(spec, mf_agg(), batch_len=64, flush_rows=150)
    assert isinstance(core, NativeResidentCore)
    # CB: max(id) is the position stat (host-free) -> 2 staged fields;
    # TB: position is ts, so id ships as a THIRD staged field
    want_fields = (("rev", "amt") if wt is WinType.CB
                   else ("id", "rev", "amt"))
    assert core._multi and core._ship_fields == want_fields
    host = run_core(WinSeqCore(spec, mf_agg()), batches)
    assert_mf_equal(host, run_core(core, batches))


def test_native_multifield_single_field_multi_op():
    """Two ops over ONE field also take the native multi path (one ring,
    two stat evaluations per dispatch)."""
    from windflow_tpu.ops.functions import MultiReducer
    agg = MultiReducer(("sum", "value", "sm"), ("max", "value", "mx"))
    spec = WindowSpec(16, 4, WinType.CB)
    batches = cb_stream(5, 600, seed=11)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        core = make_core_for(spec, agg, batch_len=64, flush_rows=150)
    assert isinstance(core, NativeResidentCore)
    assert core._multi and core._ship_fields == ("value",)
    host = run_core(WinSeqCore(spec, MultiReducer(
        ("sum", "value", "sm"), ("max", "value", "mx"))), batches)
    assert len(host) == len(core_out := run_core(core, batches))
    for f in ("key", "id", "ts", "sm", "mx"):
        np.testing.assert_array_equal(host[f], core_out[f], err_msg=f)


def test_native_multifield_per_field_wire_narrowing():
    """The C ABI narrows each staged column independently: rev in [0,50)
    ships int8 while amt spans int16 — asserted straight off
    wf_launch_peek_wires on a hand-driven core."""
    import ctypes

    from windflow_tpu import native as nat
    lib = nat.load()
    if lib is None:
        pytest.skip("native library unavailable")
    h = lib.wf_core_new(8, 8, 0, 0, 0, 1, 8, 0, 1, 8, 0, 1, 8,
                        1 << 20, 64, 2)
    try:
        mw = (ctypes.c_int * 2)(2, 2)
        lib.wf_core_set_fields(h, 2, mw)
        b = batch_from_columns(
            MF_SCHEMA, key=np.zeros(128, dtype=np.int64),
            id=np.arange(128), ts=np.arange(128),
            rev=np.full(128, 7, dtype=np.int64),
            amt=np.full(128, 30000, dtype=np.int64))
        f = b.dtype.fields
        voffs = np.array([f["rev"][1], f["amt"][1]], dtype=np.int64)
        harr = (ctypes.c_void_p * 1)(h)
        lib.wf_cores_process_mt_f(
            harr, 1, b.ctypes.data, len(b), b.dtype.itemsize,
            f["key"][1], f["id"][1], f["ts"][1], f["marker"][1],
            voffs.ctypes.data_as(nat.p_i64))
        assert lib.wf_launch_pending(h) >= 1
        wires = (ctypes.c_int * 2)()
        assert lib.wf_launch_peek_wires(h, wires) == 1
        assert list(wires) == [0, 1], "rev int8 wire, amt int16 wire"
    finally:
        lib.wf_core_free(h)


def test_native_multifield_coalescing_matches_host():
    """Queued multi-field launches merge per field (each at its own
    widened wire dtype) and stay exact: tiny flush_rows force a deep
    queue, chunks alternate narrow/wide amt ranges so the merged columns
    must widen."""
    spec = WindowSpec(16, 4, WinType.CB)
    rng = np.random.default_rng(13)
    batches = []
    for c, (lo, hi) in enumerate([(-5, 5), (-30000, 30000)] * 3):
        m = 300
        ids = np.repeat(np.arange(c * m, (c + 1) * m), 3)
        keys = np.tile(np.arange(3), m)
        batches.append(batch_from_columns(
            MF_SCHEMA, key=keys, id=ids, ts=ids,
            rev=rng.integers(0, 40, size=m * 3).astype(np.int64),
            amt=rng.integers(lo, hi, size=m * 3).astype(np.int64)))
    host = run_core(WinSeqCore(spec, mf_agg()), batches)
    nat = make_native(spec, mf_agg(), batch_len=1 << 20, flush_rows=96,
                      overlap=False)
    assert nat._multi
    merges = []
    real = nat._lib

    class _Shim:
        def __getattr__(self, name):
            if name != "wf_launch_coalesce":
                return getattr(real, name)

            def counting(h, cells, mx, mult):
                n = real.wf_launch_coalesce(h, cells, mx, mult)
                merges.append(n)
                return n
            return counting

    nat._lib = _Shim()
    assert_mf_equal(host, run_core(nat, batches))
    assert sum(merges) > 0, "multi-field launches never merged"


def test_native_multifield_sharded_and_overlap():
    """Key-sharded MT (wf_cores_process_mt_f two-phase pool) + ship
    threads compose with multi-field staging."""
    spec = WindowSpec(12, 3, WinType.CB)
    batches = mf_stream(7, 500, chunk=83, seed=29)
    host = run_core(WinSeqCore(spec, mf_agg()), batches)
    nat = make_native(spec, mf_agg(), batch_len=64, flush_rows=120,
                      shards=2, overlap=True)
    assert nat._multi and nat.shards == 2
    assert_mf_equal(host, run_core(nat, batches))


def test_native_multifield_float_routes_python():
    """Float stats keep the Python resident core (the native ABI ships
    int64 columns); >4 distinct fields too."""
    from windflow_tpu.ops.functions import MultiReducer, Reducer as R
    from windflow_tpu.patterns.win_seq_tpu import ResidentWinSeqCore
    spec = WindowSpec(16, 4, WinType.CB)
    agg = MultiReducer(R("sum", "rev", "rs"),
                       R("min", "amt", "al", dtype=np.float32))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        core = make_core_for(spec, agg, batch_len=64, flush_rows=150)
    assert isinstance(core, ResidentWinSeqCore)
    agg5 = MultiReducer(*[("max", f"f{i}", f"o{i}") for i in range(5)])
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        core5 = make_core_for(spec, agg5, batch_len=64, flush_rows=150)
    assert isinstance(core5, ResidentWinSeqCore)


def test_native_multifield_falls_back_on_nonint_column():
    """A non-int64 batch column (int32 here) under a staged stat falls
    back to the Python core transparently mid-stream — the native ABI
    ships int64 columns and its schema check is at runtime."""
    schema = Schema(rev=np.int64, amt=np.int32)
    rng = np.random.default_rng(31)
    m, nk = 400, 3
    b = batch_from_columns(
        schema, key=np.tile(np.arange(nk), m),
        id=np.repeat(np.arange(m), nk),
        ts=np.repeat(np.arange(m), nk),
        rev=rng.integers(0, 50, size=m * nk).astype(np.int64),
        amt=rng.integers(-9000, 9000, size=m * nk).astype(np.int32))
    from windflow_tpu.ops.functions import MultiReducer
    agg = MultiReducer(("sum", "rev", "rs"), ("max", "amt", "ah"),
                       dtype=np.int64)
    spec = WindowSpec(16, 4, WinType.CB)
    nat = make_native(spec, agg, batch_len=64, flush_rows=150)
    assert nat._multi
    out = nat.process(b)
    tail = nat.flush()
    assert nat._delegate is not None, "expected fallback to Python core"
    got = np.sort(np.concatenate([o for o in (out, tail) if len(o)]),
                  order=["key", "id"])
    host = run_core(WinSeqCore(spec, MultiReducer(
        ("sum", "rev", "rs"), ("max", "amt", "ah"), dtype=np.int64)), [b])
    assert len(host) == len(got)
    for f in ("key", "id", "rs"):
        np.testing.assert_array_equal(host[f], got[f], err_msg=f)


def test_native_pos_min_split_matches_host():
    """r5: MIN over the position field rides the pos-extrema split — the
    window's FIRST archived row, no column shipped — alongside MAX, on
    both TB (pos=ts) and CB (pos=id) windows, native vs host exact."""
    from windflow_tpu.ops.functions import MultiReducer
    from windflow_tpu.patterns.win_seq_tpu import split_pos_max

    # TB: first/lastUpdate style aggregate; device half = sum(value) only
    spec = WindowSpec(50, 25, WinType.TB)
    agg = MultiReducer(("count", None, "n"), ("min", "ts", "first"),
                       ("max", "ts", "last"), ("sum", "value", "sm"))
    dev, pos = split_pos_max(spec, agg)
    assert [p.field for p in dev] == ["value"]
    assert sorted(p.op for p in pos) == ["max", "min"]
    rng = np.random.default_rng(41)
    nk, per = 3, 400
    batches = []
    for lo in range(0, per, 67):
        m = min(67, per - lo)
        batches.append(batch_from_columns(
            SCHEMA, key=np.tile(np.arange(nk), m),
            id=np.repeat(np.arange(lo, lo + m), nk),
            ts=np.repeat(np.arange(lo, lo + m) * 7 + 3, nk),
            value=rng.integers(-50, 100, size=m * nk).astype(np.int64)))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        core = make_core_for(spec, agg, batch_len=32, flush_rows=150)
    assert isinstance(core, NativeResidentCore)
    host = run_core(WinSeqCore(spec, agg), batches)
    got = run_core(core, batches)
    assert len(host) == len(got)
    for f in ("key", "id", "ts", "n", "first", "last", "sm"):
        np.testing.assert_array_equal(host[f], got[f], err_msg=f)

    # CB sliding (regular-descriptor launches must carry hpmin too) —
    # and an ENTIRELY host-free aggregate routes to the host core
    from windflow_tpu.core.winseq import WinSeqCore as HostCore
    spec = WindowSpec(16, 4, WinType.CB)
    cb = MultiReducer(("min", "id", "lo"), ("max", "id", "hi"),
                      ("sum", "value", "sm"))
    batches = cb_stream(4, 700, chunk=128, seed=47)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        core = make_core_for(spec, cb, batch_len=1 << 20, flush_rows=200)
    assert isinstance(core, NativeResidentCore)
    host = run_core(HostCore(spec, cb), batches)
    got = run_core(core, batches)
    for f in ("key", "id", "lo", "hi", "sm"):
        np.testing.assert_array_equal(host[f], got[f], err_msg=f)
    free = MultiReducer(("count", None, "n"), ("min", "id", "lo"),
                        ("max", "id", "hi"))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        hostish = make_core_for(spec, free, batch_len=64)
    assert not isinstance(hostish, NativeResidentCore), \
        "fully pos-free aggregate should route to the host core"


def test_posfree_aggregate_forced_device_routes_python():
    """A fully pos-free MultiReducer FORCED onto the device
    (use_resident=True past the host route) needs the Python core's
    ship-the-position-column fallback — the native gate must not claim
    it (review r5: dev_parts empty slipped the vacuous field-count
    clause and raised in NativeResidentCore.__init__)."""
    from windflow_tpu.ops.functions import MultiReducer
    from windflow_tpu.patterns.win_seq_tpu import ResidentWinSeqCore
    free = MultiReducer(("count", None, "n"), ("min", "id", "lo"),
                        ("max", "id", "hi"))
    spec = WindowSpec(16, 4, WinType.CB)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        core = make_core_for(spec, free, batch_len=64, flush_rows=150,
                             use_resident=True)
    assert isinstance(core, ResidentWinSeqCore)
    batches = cb_stream(3, 300, chunk=71, seed=53)
    host = run_core(WinSeqCore(spec, MultiReducer(
        ("count", None, "n"), ("min", "id", "lo"),
        ("max", "id", "hi"))), batches)
    got = run_core(core, batches)
    assert len(host) == len(got)
    for f in ("key", "id", "n", "lo", "hi"):
        np.testing.assert_array_equal(host[f], got[f], err_msg=f)


def test_native_abi_guards():
    """ABI misuse is a defined error, not UB: the single-field process
    entry on a multi-field core returns -1 (review r5: it previously
    dereferenced the missing offsets), and wf_core_set_fields reports
    the accepted count so callers can refuse a short accept."""
    import ctypes

    from windflow_tpu import native as nat
    lib = nat.load()
    if lib is None:
        pytest.skip("native library unavailable")
    assert int(lib.wf_max_fields()) == 4
    h = lib.wf_core_new(8, 8, 0, 0, 0, 1, 8, 0, 1, 8, 0, 1, 8,
                        1 << 20, 64, 2)
    try:
        mw = (ctypes.c_int * 2)(2, 2)
        assert lib.wf_core_set_fields(h, 2, mw) == 2
        assert lib.wf_core_set_fields(h, 9, None) == 4  # clamped accept
        lib.wf_core_set_fields(h, 2, mw)
        b = batch_from_columns(
            MF_SCHEMA, key=np.zeros(16, dtype=np.int64),
            id=np.arange(16), ts=np.arange(16),
            rev=np.ones(16, dtype=np.int64),
            amt=np.ones(16, dtype=np.int64))
        f = b.dtype.fields
        got = lib.wf_core_process(
            h, b.ctypes.data, len(b), b.dtype.itemsize, f["key"][1],
            f["id"][1], f["ts"][1], f["marker"][1], f["rev"][1])
        assert got == -1, "single-field entry on a 2-field core must refuse"
    finally:
        lib.wf_core_free(h)


# ------------------------------------------------- state ABI (ISSUE 17)

def _abi_source_constant():
    import os
    import re
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "native", "wf_native.cpp")
    with open(src) as f:
        m = re.search(r"kStateAbiVersion\s*=\s*(\d+)", f.read())
    assert m, "kStateAbiVersion constant missing from wf_native.cpp"
    return int(m.group(1))


def test_abi_version_matches_source():
    """The loaded .so's wf_abi_version() equals the kStateAbiVersion
    constant in wf_native.cpp — a forgotten rebuild after an ABI bump
    would silently import incompatible blobs otherwise."""
    lib = native.load()
    assert getattr(lib, "wf_has_state_abi", False), (
        "the built library must export the state ABI")
    assert int(lib.wf_abi_version()) == _abi_source_constant()


def test_bind_tolerates_pre_abi_library(monkeypatch):
    """_bind over a library missing the state symbols (a stale .so from
    before this ABI) must succeed with wf_has_state_abi=False instead of
    raising — default paths keep the old library serviceable."""
    _STATE_SYMS = {
        "wf_abi_version", "wf_core_state_size", "wf_core_state_export",
        "wf_core_state_import", "wf_core_key_count", "wf_core_key_list",
        "wf_core_key_state_size", "wf_core_key_export",
        "wf_core_key_import", "wf_core_key_neutralize"}

    class _Fn:
        restype = None
        argtypes = None

    class _OldLib:
        def __getattr__(self, name):
            if name in _STATE_SYMS:
                raise AttributeError(name)
            fn = _Fn()
            self.__dict__[name] = fn
            return fn

    # _bind assigns the module-global _lib; snapshot + restore it
    monkeypatch.setattr(native, "_lib", native._lib)
    lib = native._bind(_OldLib())
    assert lib.wf_has_state_abi is False
    assert lib.wf_has_overload_queue is True


def _dense_stream(n_batches=12, rows=40, n_keys=5, seed=3):
    """Per-key dense ids / monotone ts (the pristine-source contract)."""
    rng = np.random.default_rng(seed)
    ctr = {}
    out = []
    for _ in range(n_batches):
        b = np.zeros(rows, dtype=SCHEMA.dtype())
        keys = rng.integers(0, n_keys, rows)
        b["key"] = keys
        b["value"] = rng.integers(-50, 100, rows)
        for i, k in enumerate(keys.tolist()):
            b["id"][i] = ctr.get(k, 0)
            ctr[k] = ctr.get(k, 0) + 1
        b["ts"] = b["id"]
        out.append(b)
    return out


@pytest.mark.parametrize("shards", [1, 2, 3])
def test_native_state_roundtrip_byte_identical(shards):
    """Crash differential at the core level: run A drains + snapshots at
    a barrier and continues; run B snapshots the same barrier, then a
    FRESH core restores the blob and replays the tail.  Emission streams
    must be byte-identical, batch boundaries included."""
    spec = WindowSpec(8, 4, WinType.CB)
    batches = _dense_stream()
    cut = 6

    def fresh():
        return make_native(spec, Reducer("sum", "value"), batch_len=32,
                           flush_rows=64, shards=shards,
                           overlap=(shards > 1))

    def run(core, bs):
        out = []
        for b in bs:
            out.extend(core.process_batches(b))
        return out

    a = fresh()
    out_a = run(a, batches[:cut])
    out_a.extend(a.checkpoint_drain_batches())
    a.state_snapshot()
    out_a.extend(run(a, batches[cut:]))
    out_a.extend(a.flush_batches())

    b = fresh()
    out_b = run(b, batches[:cut])
    out_b.extend(b.checkpoint_drain_batches())
    snap = b.state_snapshot()
    r = fresh()                      # the restarted worker
    r.state_restore(snap)
    out_b.extend(run(r, batches[cut:]))
    out_b.extend(r.flush_batches())

    assert [x.tobytes() for x in out_a] == [x.tobytes() for x in out_b]


def _cut_stream(wt):
    """Three keys, ids running on over three chunks; the cut falls after the
    second, inside open windows: the first window to fire after it (ids 4-11
    of a key, closed by id 12) has its last row before it."""
    rng = np.random.default_rng(5)
    bs = [_periodic(3, lo, m, rng) for lo, m in ((0, 7), (7, 5), (12, 19))]
    spec = (WindowSpec(8, 4, wt) if wt is WinType.CB
            else WindowSpec(56, 28, wt))
    return spec, bs, 2


@pytest.mark.parametrize("per_key", [False, True], ids=["core", "key"])
@pytest.mark.parametrize("wt", [WinType.CB, WinType.TB], ids=["cb", "tb"])
def test_native_state_carries_the_result_ts(wt, per_key):
    """Exported mid-window and imported into a fresh core -- whole-core blob
    or key by key (export, neutralize, import) -- the stream goes on to the
    results of a core that never stopped, ts included: the newest archived
    row's position and ts travel in the blob, the ts column does not
    exist."""
    spec, batches, cut = _cut_stream(wt)

    def fresh():
        return make_native(spec, Reducer("sum", "value"), batch_len=32,
                           flush_rows=64)

    def run(core, bs):
        return [o for b in bs for o in core.process_batches(b)]

    whole = fresh()
    want = run(whole, batches) + whole.flush_batches()
    a = fresh()
    got = run(a, batches[:cut]) + a.checkpoint_drain_batches()
    b = fresh()
    if per_key:
        keys = a.keyed_state_keys()
        assert list(keys) == [0, 1, 2]
        b.keyed_state_import(a.keyed_state_export(keys))
        assert len(a.keyed_state_keys()) == 0
        assert a.flush_batches() == []    # a neutralized key fires no more
    else:
        b.state_restore(a.state_snapshot())
    got += run(b, batches[cut:]) + b.flush_batches()
    want, got = (np.sort(np.concatenate(x), order=["key", "id"])
                 for x in (want, got))
    assert_equal_results(want, got)
    if wt is WinType.CB:
        # window 1 of key 0: rows 4-11, the last of them before the cut
        w1 = got[(got["key"] == 0) & (got["id"] == 1)]
        assert int(w1["ts"][0]) == int(_ts_of(0, 11))


def test_state_abi_is_version_2():
    """Version 2: a key's record carries tail_pos / tail_ts and a ts array
    only on a count-based arg-extremum core."""
    assert _abi_source_constant() == 2
    assert int(native.load().wf_abi_version()) == 2


@pytest.mark.parametrize("per_key", [False, True], ids=["core", "key"])
def test_a_version_1_blob_is_refused(per_key):
    """An older library's blob (its ts array in every record, no tail) is
    refused by its version stamp, code -4, and leaves the core as it was."""
    spec, batches, cut = _cut_stream(WinType.CB)
    a = make_native(spec, Reducer("sum", "value"), batch_len=32,
                    flush_rows=64)
    for b in batches[:cut]:
        a.process_batches(b)
    a.checkpoint_drain_batches()

    def stamped_1(blob):
        words = np.frombuffer(blob, dtype=np.int64).copy()
        assert words[1] == 2
        words[1] = 1
        return words.tobytes()

    fresh = make_native(spec, Reducer("sum", "value"), batch_len=32,
                        flush_rows=64)
    with pytest.raises(RuntimeError, match=r"code -4"):
        if per_key:
            frag = a.keyed_state_export([0])
            frag["blobs"] = {k: stamped_1(v)
                             for k, v in frag["blobs"].items()}
            fresh.keyed_state_import(frag)
        else:
            snap = a.state_snapshot().resolve()
            snap["blobs"] = tuple(stamped_1(v) for v in snap["blobs"])
            fresh.state_restore(snap)
    assert len(fresh.keyed_state_keys()) == 0


def test_native_state_export_requires_drain():
    """wf_core_state_export refuses an undrained core: pending rows not
    yet flushed to launches would be silently dropped by the blob."""
    core = make_native(WindowSpec(8, 4, WinType.CB),
                       Reducer("sum", "value"), batch_len=32,
                       flush_rows=1 << 20)
    core.process(_dense_stream(n_batches=1)[0])
    with pytest.raises(RuntimeError, match="not drained"):
        core.state_snapshot()
    core.checkpoint_drain_batches()
    core.state_snapshot()            # drained now: export succeeds


def _per_key(rows):
    d = {}
    for r in rows:
        d.setdefault(int(r["key"]), []).append(
            (int(r["id"]), int(r["value"])))
    return d


def test_native_keyed_migration_per_key_equal():
    """Key_Farm migration at a barrier: export+neutralize moving keys on
    the old owner, import on the new owner, feed the tail to the new
    owner — merged per-key result sequences equal the single-core
    oracle's."""
    spec = WindowSpec(8, 4, WinType.CB)
    batches = _dense_stream(n_keys=4)
    cut = 6
    reducer = Reducer("sum", "value")

    oracle = make_native(spec, reducer, batch_len=32, flush_rows=64)
    want = []
    for b in batches:
        want.extend(oracle.process_batches(b))
    want.extend(oracle.flush_batches())
    want = _per_key(np.concatenate([x for x in want if len(x)]))

    w0 = make_native(spec, reducer, batch_len=32, flush_rows=64)
    w1 = make_native(spec, reducer, batch_len=32, flush_rows=64)
    owner = {0: w0, 1: w0, 2: w1, 3: w1}   # pre-cut routing
    got = []

    def feed(b):
        for w in (w0, w1):
            mask = np.isin(b["key"], [k for k, o in owner.items()
                                      if o is w])
            got.extend(w.process_batches(b[mask]))

    for b in batches[:cut]:
        feed(b)
    # the barrier: both drained, keys 0/1 migrate w0 -> w1
    got.extend(w0.checkpoint_drain_batches())
    got.extend(w1.checkpoint_drain_batches())
    assert sorted(w0.keyed_state_keys()) == [0, 1]
    frag = w0.keyed_state_export([0, 1])
    assert frag["kind"] == "native_keys"
    w1.keyed_state_import(frag)
    assert list(w0.keyed_state_keys()) == []   # neutralized on export
    owner[0] = owner[1] = w1
    for b in batches[cut:]:
        feed(b)
    got.extend(w0.flush_batches())
    got.extend(w1.flush_batches())
    got = _per_key(np.concatenate([x for x in got if len(x)]))
    assert got == want


def test_native_stale_so_core_declines_loudly():
    """A core bound against a pre-ABI library (simulated by the flags
    _bind would have left) declines snapshots and migration with
    SnapshotUnsupported while default execution is unchanged."""
    from windflow_tpu.runtime.node import SnapshotUnsupported
    spec = WindowSpec(8, 4, WinType.CB)
    batches = _dense_stream()
    core = make_native(spec, Reducer("sum", "value"), batch_len=32,
                       flush_rows=64)
    core.has_state_abi = False
    core.keyed_migratable = False
    for what in (core.state_snapshot, core.keyed_state_keys,
                 lambda: core.keyed_state_export([0]),
                 lambda: core.keyed_state_import({"kind": "native_keys"}),
                 lambda: core.state_restore({"kind": "native"})):
        with pytest.raises(SnapshotUnsupported, match="state ABI"):
            what()
    host = run_core(WinSeqCore(spec, Reducer("sum", "value")), batches)
    assert_equal_results(host, run_core(core, batches))


# ---- build / bind failures are loud (no quiet switch to the Python cores)

def _fresh_loader(monkeypatch):
    """native.load() as a new process would see it; monkeypatch restores
    the really-loaded library afterwards."""
    monkeypatch.setattr(native, "_lib", None)
    monkeypatch.setattr(native, "_tried", False)
    monkeypatch.setattr(native, "_error", None)


def test_failing_make_with_source_present_is_loud(monkeypatch):
    import subprocess
    _fresh_loader(monkeypatch)
    calls = []

    def failing_make(cmd, **kw):
        calls.append(cmd)
        return subprocess.CompletedProcess(
            cmd, 2, stdout="", stderr="wf_native.cpp:1:1: error: boom")

    monkeypatch.setattr(native.subprocess, "run", failing_make)
    monkeypatch.delenv("WF_NO_NATIVE", raising=False)
    with pytest.raises(native.NativeBuildError, match="error: boom"):
        native.load()
    # every later selection point fails the same way, without re-running
    # make: no dataflow carries on with Python cores after a failed build
    with pytest.raises(native.NativeBuildError, match="exit 2"):
        native.enabled()
    with pytest.raises(native.NativeBuildError):
        make_core_for(WindowSpec(16, 4, WinType.CB), Reducer("sum"))
    assert len(calls) == 1 and calls[0][0] == "make"
    # the explicit opt-out still selects the Python cores, silently
    monkeypatch.setenv("WF_NO_NATIVE", "1")
    assert native.enabled() is None
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        core = make_core_for(WindowSpec(16, 4, WinType.CB), Reducer("sum"))
    assert type(core).__name__ == "ResidentWinSeqCore"


def test_missing_toolchain_is_loud(monkeypatch):
    _fresh_loader(monkeypatch)

    def no_make(cmd, **kw):
        raise FileNotFoundError(2, "No such file or directory: 'make'")

    monkeypatch.setattr(native.subprocess, "run", no_make)
    with pytest.raises(native.NativeBuildError, match="could not run"):
        native.load()


def test_library_that_does_not_bind_is_loud(monkeypatch):
    _fresh_loader(monkeypatch)

    def bad_dlopen(path):
        raise OSError(f"{path}: file too short")

    monkeypatch.setattr(native.ctypes, "CDLL", bad_dlopen)
    with pytest.raises(native.NativeBuildError, match="does not bind"):
        native.load()


def test_checkout_without_native_source_runs_python_cores(monkeypatch,
                                                          tmp_path):
    """No wf_native.cpp is not a failure: nothing was there to build."""
    _fresh_loader(monkeypatch)
    monkeypatch.setattr(native, "_DIR", str(tmp_path))
    assert native.load() is None and native.enabled() is None
