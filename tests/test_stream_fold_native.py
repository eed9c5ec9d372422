"""``VecStreamCore``'s native fold (``wf_sfold``: one C++ pass a stretch
between two window boundaries, a hash index of key -> slot) against its numpy
fold (``_fold``: sort, group, ``SlotMap``'s sorted view), which stays as the
reference: the result batches of every chunk equal AS ARRAYS -- the same rows
in the same order, which is slot order, so the slots are numbered alike --
and so are the counters a node reports."""

import copy
import json

import numpy as np
import pytest

from windflow_tpu import native
from windflow_tpu.core.tuples import (MARKER_FIELD, Schema,
                                      batch_from_columns, progress_row)
from windflow_tpu.core.vecinc import VecStreamCore
from windflow_tpu.core.windows import WindowSpec, WinType
from windflow_tpu.ops.functions import MultiReducer, Reducer

VALUE = Schema(value=np.int64)
STATS = MultiReducer(Reducer("count", out_field="count"),
                     Reducer("sum", "value", "total"),
                     Reducer("min", "value", "least"),
                     Reducer("max", "ts", "last"))
COUNTERS = ("keys_live", "keys_live_peak", "keys_retired", "stream_fires",
            "stream_fire_rows", "late_rows")


def cores(win, slide, holdback=0, winfunc=STATS):
    """The same core twice: as it chooses for itself, and held to numpy."""
    spec = WindowSpec(win, slide, WinType.TB)
    ours = VecStreamCore(spec, winfunc, holdback=holdback)
    ref = VecStreamCore(spec, winfunc, holdback=holdback)
    ref._native = False
    return ours, ref


def stream(seed, n=4000, span=600, disorder=0, t0=0, keys_per_unit=3):
    """A key space that grows and goes quiet; ``disorder`` > 0 moves one
    row in four back by up to that much."""
    rng = np.random.default_rng(seed)
    ts = np.sort(rng.integers(t0, t0 + span, n))
    keys = (ts - t0) // keys_per_unit * 4 + rng.integers(0, 4, n) * 4 + 1
    if disorder:
        back = rng.integers(0, 4, n) == 0
        ts = ts - back * rng.integers(0, disorder + 1, n)
    return batch_from_columns(VALUE, key=keys, id=np.arange(n), ts=ts,
                              value=rng.integers(-50, 100, n))


def same(a, b):
    assert a.dtype == b.dtype and a.shape == b.shape
    assert a.tobytes() == b.tobytes()


def feed_both(ours, ref, batch, steps, native_path=True):
    """Chunk by chunk through both cores; every output equal as arrays."""
    lo = calls = 0
    for step in steps:
        if lo >= len(batch):
            break
        chunk = batch[lo:lo + step]
        same(ours.process(chunk), ref.process(chunk))
        lo += step
        calls += 1
    assert lo >= len(batch)
    for name in COUNTERS:
        assert getattr(ours, name) == getattr(ref, name), name
    n = ours.keys_live
    assert np.array_equal(ours._slotmap.keys[:n], ref._slotmap.keys[:n])
    assert ours.fold_native_batches == (calls if native_path else 0)
    assert ref.fold_native_batches == 0
    return calls


def random_steps(seed, hi=700):
    rng = np.random.default_rng(seed)
    return (int(s) for s in rng.integers(1, hi, 10 ** 6))


# win, slide, holdback, disorder, t0
SHAPES = [
    (10, 5, 0, 0, 0),           # sliding, in order
    (10, 10, 0, 0, 0),          # tumbling
    (12, 5, 0, 0, 0),           # a window that is no whole number of slides
    (7, 7, 0, 4, 0),            # tumbling, no hold-back: rows come late
    (10, 5, 8, 8, 0),           # the hold-back covers the disorder
    (10, 5, 3, 9, 0),           # it does not: late rows
    (20, 5, 7, 7, -300),        # negative times under a hold-back
    (9, 4, 30, 25, -50),        # many lanes (W = 10)
]


@pytest.mark.parametrize("seed", [1, 2, 3])
@pytest.mark.parametrize("win,slide,holdback,disorder,t0", SHAPES)
def test_native_fold_equals_the_numpy_fold(win, slide, holdback, disorder,
                                           t0, seed):
    ours, ref = cores(win, slide, holdback)
    batch = stream(seed, disorder=disorder, t0=t0)
    feed_both(ours, ref, batch, random_steps(seed))
    if disorder > holdback:
        assert ours.late_rows > 0
    same(ours.flush(), ref.flush())
    assert ours.keys_live == ref.keys_live == 0


def test_marker_rows_inside_a_chunk_move_the_clock_and_fold_nothing():
    ours, ref = cores(10, 5)
    batch = stream(4, n=3000)
    mark = np.arange(len(batch)) % 7 == 3
    batch[MARKER_FIELD] = mark
    # some markers run ahead of the rows around them: they close windows
    batch["ts"][np.flatnonzero(mark)[::5]] += 6
    batch["ts"] = np.maximum.accumulate(batch["ts"])
    feed_both(ours, ref, batch, random_steps(4))
    out = ref.flush()
    same(ours.flush(), out)
    # a chunk of nothing but markers is a batch folded all the same
    only = progress_row(batch.dtype, 0, 10 ** 6)
    same(ours.process(only), ref.process(only))


def test_a_chunk_that_crosses_two_boundaries_fires_twice():
    ours, ref = cores(10, 5)
    batch = stream(5, n=2000, span=40)
    calls = feed_both(ours, ref, batch, [len(batch)])
    assert calls == 1 and ours.stream_fires >= 6
    same(ours.flush(), ref.flush())


def test_a_key_retired_and_seen_again_is_a_new_key():
    ours, ref = cores(10, 5)
    ts = np.array([0, 1, 2, 3, 40, 41, 42, 80, 81])
    keys = np.array([9, 5, 9, 7, 5, 9, 3, 9, 5])
    batch = batch_from_columns(VALUE, key=keys, id=np.arange(9), ts=ts,
                               value=np.arange(9))
    feed_both(ours, ref, batch, [4, 3, 2])
    assert ours.keys_retired >= 5
    same(ours.flush(), ref.flush())


@pytest.mark.parametrize("first", [0, 3000])
def test_a_chunk_of_1e5_new_keys_grows_the_lanes_and_the_index(first):
    """The lanes, the slot -> key column and the index all grow for one
    chunk, from nothing (``first`` 0) or while they hold keys; the second
    big chunk brings the same keys again and as many new ones."""
    ours, ref = cores(1000, 500)
    rng = np.random.default_rng(6)
    n = 100_000
    warmed = 0
    if first:
        warm = stream(6, n=first, span=400)
        warmed = feed_both(ours, ref, warm, [700] * 9)
    keys = rng.permutation(n) * 8 + 3
    a = batch_from_columns(VALUE, key=keys, id=np.arange(n),
                           ts=400 + np.arange(n) // 1000,
                           value=rng.integers(0, 9, n))
    b = batch_from_columns(VALUE, key=np.concatenate((keys[::2],
                                                      keys[::2] + 10 ** 7)),
                           id=np.arange(n), ts=520 + np.arange(n) // 1000,
                           value=rng.integers(0, 9, n))
    cap0 = len(ours._tab)
    same(ours.process(a), ref.process(a))
    same(ours.process(b), ref.process(b))
    assert len(ours._tab) >= 2 * ours.keys_live > cap0
    assert ours.keys_live == ref.keys_live >= 150_000
    assert ours.fold_native_batches == 2 + warmed
    same(ours.flush(), ref.flush())


def test_a_hot_key_of_1e5_rows_among_cold_ones():
    ours, ref = cores(10_000, 5_000)
    rng = np.random.default_rng(7)
    n = 125_000
    keys = np.where(rng.integers(0, 5, n) > 0, 44, rng.integers(0, 4000, n))
    batch = batch_from_columns(VALUE, key=keys, id=np.arange(n),
                               ts=np.arange(n) // 13,
                               value=rng.integers(0, 1000, n))
    feed_both(ours, ref, batch, [50_000, 50_000, 25_000])
    out = ref.flush()
    same(ours.flush(), out)
    hot = out[out["key"] == 44]
    assert hot["count"][0] == (keys == 44).sum() >= 99_000


def test_a_deep_copy_taken_mid_stream_restores_to_the_same_results():
    ours, ref = cores(10, 5, holdback=4)
    batch = stream(8, disorder=4)
    half = len(batch) // 2
    feed_both(ours, ref, batch[:half], random_steps(8))
    snap = copy.deepcopy(ours)
    assert len(snap._tab) == 0 < len(ours._tab)     # rebuilt from the keys
    tail = [ours.process(batch[half:]), ours.flush()]
    again = copy.deepcopy(snap)                     # the snapshot stays whole
    for core in (snap, again):
        same(core.process(batch[half:]), tail[0])
        same(core.flush(), tail[1])
    same(ref.process(batch[half:]), tail[0])
    same(ref.flush(), tail[1])
    assert snap.fold_native_batches == ours.fold_native_batches


def test_a_copy_flushed_before_its_next_chunk_retires_on_a_fresh_index():
    ours, ref = cores(10, 5)
    batch = stream(13)
    feed_both(ours, ref, batch, random_steps(13))
    assert ours.keys_live > 0
    snap = copy.deepcopy(ours)
    same(snap.flush(), ref.flush())
    assert snap.keys_live == 0 and len(snap._tab) >= 2


def brute(batch, win, slide, value=int):
    out = {}
    for r in batch[~batch[MARKER_FIELD]]:
        k, t = int(r["key"]), int(r["ts"])
        for w in range(max((t - win) // slide + 1, 0), t // slide + 1):
            c = out.setdefault((k, w), [0, value(0)])
            c[0] += 1
            c[1] += value(r["value"])
    return out


def as_dict(rows):
    real = rows[~rows[MARKER_FIELD]]
    return {(int(r["key"]), int(r["id"])): [int(r["count"]), r["total"].item()]
            for r in real}


COUNT_SUM = MultiReducer(Reducer("count", out_field="count"),
                         Reducer("sum", "value", "total"))


def test_without_the_library_the_numpy_fold_runs_and_is_right(monkeypatch):
    monkeypatch.setenv("WF_NO_NATIVE", "1")
    core = VecStreamCore(WindowSpec(10, 5, WinType.TB), COUNT_SUM)
    batch = stream(9, n=1500)
    out = np.concatenate((core.process(batch[:800]), core.process(batch[800:]),
                          core.flush()))
    assert core._native is False and core.fold_native_batches == 0
    assert as_dict(out) == brute(batch, 10, 5)


@pytest.mark.parametrize("field,acc", [(np.float64, np.float64),
                                       (np.float32, np.int64),
                                       (np.int64, np.int32)])
def test_a_float_field_or_a_narrow_lane_takes_the_numpy_fold(field, acc):
    """What the call cannot do exactly as numpy does, numpy does: a float
    field (whatever the lanes), lanes that are not int64."""
    winfunc = MultiReducer(Reducer("count", out_field="count"),
                           Reducer("sum", "value", "total", dtype=acc))
    core = VecStreamCore(WindowSpec(10, 5, WinType.TB), winfunc)
    src = stream(10, n=1500)
    batch = np.zeros(len(src), dtype=Schema(value=field).dtype())
    for name in batch.dtype.names:
        batch[name] = src[name]
    out = np.concatenate((core.process(batch[:700]), core.process(batch[700:]),
                          core.flush()))
    assert core._native is False and core.fold_native_batches == 0
    assert as_dict(out) == brute(batch, 10, 5,
                                 value=float if acc == np.float64 else int)


@pytest.mark.parametrize("field", [np.int8, np.uint8, np.int16, np.uint16,
                                   np.int32, np.uint32, np.uint64])
def test_an_integer_field_of_any_width_folds_natively(field):
    """Signed and unsigned fields narrower than the lanes, read from a
    packed record at whatever alignment, through a strided view."""
    ours, ref = cores(10, 5)
    src = stream(11, n=3000)
    dtype = np.dtype([("key", np.int64), ("id", np.int64), (MARKER_FIELD, "?"),
                      ("pad", "u1", (3,)), ("value", field),
                      ("ts", np.int64)])
    wide = np.zeros(2 * len(src), dtype=dtype)
    batch = wide[::2]                       # a stride of two records
    info = np.iinfo(field)
    rng = np.random.default_rng(11)
    for name in ("key", "id", "ts"):
        batch[name] = src[name]
    batch["value"] = rng.integers(info.min, info.max, len(src), dtype=field,
                                  endpoint=True)
    feed_both(ours, ref, batch, random_steps(11))
    same(ours.flush(), ref.flush())


def test_an_int64_sum_wraps_as_numpy_does():
    ours, ref = cores(10, 5)
    big = np.iinfo(np.int64).max
    batch = batch_from_columns(VALUE, key=[1, 1, 1, 2], id=np.arange(4),
                               ts=[0, 1, 2, 3], value=[big, big, 5, -big])
    feed_both(ours, ref, batch, [4])
    same(ours.flush(), ref.flush())


def test_a_stream_that_changes_its_record_falls_to_numpy_and_stays_right():
    ours, ref = cores(10, 5, winfunc=COUNT_SUM)
    src = stream(12, n=3000)
    half = len(src) // 2
    feed_both(ours, ref, src[:half], [600] * 3)
    floats = np.zeros(len(src) - half, dtype=Schema(value=np.float64).dtype())
    for name in floats.dtype.names:
        floats[name] = src[half:][name]
    same(ours.process(floats), ref.process(floats))
    assert ours._native is False and ours.fold_native_batches == 3
    same(ours.process(src[half:][:0]), ref.process(src[half:][:0]))
    same(ours.flush(), ref.flush())


def test_the_path_is_chosen_from_what_the_core_can_observe():
    plan = VecStreamCore(WindowSpec(10, 5, WinType.TB),
                         STATS)._native_plan(VALUE.dtype())
    assert native.enabled() is not None and plan is not None
    prod = MultiReducer(Reducer("count", out_field="count"),
                        Reducer("prod", "value", "p"))
    assert VecStreamCore(WindowSpec(10, 5, WinType.TB),
                         prod)._native_plan(VALUE.dtype()) is None


def test_a_node_reports_the_batches_its_core_folded_natively(tmp_path):
    from test_stream_fire import bids, run_hot_items
    rows = bids(8)
    run_hot_items(rows, 900, 4, trace_dir=str(tmp_path))
    workers = [json.loads(p.read_text())
               for p in tmp_path.glob("*count.[0-3].log")]
    assert len(workers) == 4
    for w in workers:
        assert w["fold_native_batches"] == (w["non_triggering_batches"]
                                            + w["triggering_batches"]) > 0
