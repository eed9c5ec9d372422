"""The two-sided window stage (``WinJoinTPU``: NEXMark Q8's tumbling-window
join of persons against auctions) on the CPU at small sizes: the pattern
through ``MultiPipe`` and the public builder against the brute-force join of
``tests/oracle.py`` on seeded streams -- both sides present, a window
without a person, one without an auction, a hot seller, a seller whose person
arrives after its auctions, one created in the window before, chunks that
straddle a window's end, carried fields of both sides; a result exactly at
and one past its slots; a duplicate left key; what is refused and how; the
counters, the spans and the steps the launches run; and the benchmark's
``q8_new_users`` configuration against its plain reference.
"""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
for _p in (ROOT, BENCH, os.path.dirname(os.path.abspath(__file__))):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import oracle  # noqa: E402
from configs import q8_new_users, q8_new_users_oracle  # noqa: E402
from harness import check  # noqa: E402

from windflow_tpu.api import MultiPipe, WinJoinTPU_Builder  # noqa: E402
from windflow_tpu.core.tuples import MARKER_FIELD, Schema  # noqa: E402
from windflow_tpu.core.windows import WindowSpec, WinType  # noqa: E402
from windflow_tpu.ops import resident  # noqa: E402
from windflow_tpu.ops.device import _bucket_fine  # noqa: E402
from windflow_tpu.patterns.basic import Filter, Sink, Source  # noqa: E402
from windflow_tpu.patterns.win_join_tpu import (  # noqa: E402
    WinJoinCore, WinJoinTPU)
from windflow_tpu.patterns.win_seq import window_cores  # noqa: E402
from windflow_tpu.patterns.win_seq_tpu import plan_core  # noqa: E402
from windflow_tpu.recovery.policy import RecoveryPolicy  # noqa: E402
from windflow_tpu.utils import profile  # noqa: E402

PERSON, AUCTION, BID = 0, 1, 2
SCHEMA = Schema(event_type=np.int8, person=np.int64, auction=np.int64,
                seller=np.int64, reserve=np.int64, city=np.int64)
WIN = 1000
JOIN = dict(side_field="event_type", left=(PERSON, "person"),
            right=(AUCTION, "seller"), key_range=(0, 1 << 20),
            right_fields=("auction", "reserve"),
            field_ranges={"auction": (0, 1 << 20), "reserve": (0, 1 << 28),
                          "city": (0, 100)},
            window_rows=2048, max_results=1024, flush_rows=256)
ORACLE = dict(side="event_type", left=(PERSON, "person"),
              right=(AUCTION, "seller"), right_fields=("auction", "reserve"))


@pytest.fixture(autouse=True)
def _profile_state():
    profile.disable()
    profile.reset()
    yield
    profile.auto()
    profile.reset()


def _events(kinds, ts, persons=None, sellers=None, seed=0):
    """Rows of a NEXMark-like stream: ``kinds[i]`` the event's type,
    ``ts[i]`` its time; a person's id runs from 1000 unless given, an
    auction's seller is drawn among the persons so far unless given."""
    rng = np.random.default_rng(seed)
    n = len(kinds)
    rows = np.zeros(n, dtype=SCHEMA.dtype())
    rows["id"] = np.arange(n)
    rows["ts"] = ts
    rows["event_type"] = kinds
    is_p, is_a = rows["event_type"] == PERSON, rows["event_type"] == AUCTION
    pid = 999 + np.cumsum(is_p)
    rows["person"] = np.where(is_p, pid if persons is None else persons, 0)
    drawn = np.maximum(pid - rng.integers(0, 40, n), 1000)
    rows["seller"] = np.where(is_a, drawn if sellers is None else sellers, 0)
    rows["auction"] = np.where(is_a, 5000 + np.arange(n), 0)
    rows["reserve"] = np.where(is_a, rng.integers(200, 1 << 27, n), 0)
    rows["city"] = np.where(is_p, rng.integers(0, 100, n), 0)
    return rows


def _nexmark(n=6000, step=1, seed=0):
    """1 person, 3 auctions and 46 bids in 50 events, ``step`` time units an
    event."""
    rem = np.arange(n) % 50
    kinds = np.where(rem < 1, PERSON, np.where(rem < 4, AUCTION, BID))
    return _events(kinds, np.arange(n) * step, seed=seed)


def _chunks(rows, chunk):
    return [rows[lo:lo + chunk].copy() for lo in range(0, len(rows), chunk)]


def _run(rows, chunk=700, join=None, pattern=None, **pipe_kw):
    """The stream through Source > Filter > the join > Sink; the sink's
    batches."""
    got = []
    pattern = pattern or WinJoinTPU(WIN, **dict(JOIN, **(join or {})))
    pipe = (MultiPipe("join", **pipe_kw)
            .add_source(Source(batches=_chunks(rows, chunk), schema=SCHEMA))
            .chain(Filter(lambda b: b["event_type"] != BID, vectorized=True))
            .add(pattern)
            .add_sink(Sink(lambda b: got.append(b.copy())
                           if b is not None and len(b) else None,
                           vectorized=True)))
    pipe.run_and_wait_end()
    return got, pipe


def _table(batches, fields=("auction", "reserve")):
    if not batches:
        return []
    out = np.concatenate(batches)
    assert not out[MARKER_FIELD].any()
    return list(zip(*(out[f].tolist() for f in ("key", "id", "ts") + fields)))


def _kinds(*runs):
    return np.concatenate([np.full(n, k) for k, n in runs])


# -- the stream shapes the issue names, each against the brute force --------

def _both_sides():
    return _nexmark()


def _window_without_person():
    rows = _nexmark(4000)
    quiet = (rows["ts"] // WIN == 1) & (rows["event_type"] == PERSON)
    return rows[~quiet]


def _window_without_auction():
    rows = _nexmark(4000)
    quiet = (rows["ts"] // WIN == 2) & (rows["event_type"] == AUCTION)
    return rows[~quiet]


def _hot_seller():
    rows = _nexmark(5000, seed=3)
    is_a = rows["event_type"] == AUCTION
    pid = 999 + np.cumsum(rows["event_type"] == PERSON)
    hot = np.maximum(pid // 10 * 10, 1000)         # the newest multiple of 10
    rows["seller"] = np.where(is_a & (np.arange(len(rows)) % 4 > 0), hot,
                              rows["seller"])
    return rows


def _person_after_its_auctions():
    # two auctions of seller 7 at 100 and 200, the person at 900: one window
    kinds = _kinds((AUCTION, 2), (BID, 5), (PERSON, 1), (AUCTION, 1))
    return _events(kinds, [100, 200, 300, 310, 320, 330, 340, 900, 950],
                   persons=7, sellers=7)


def _seller_of_the_window_before():
    # person 7 at 900 (window 0), its auctions at 1100 and 1200 (window 1):
    # nothing; person 8 and its auction in window 1: one result
    kinds = _kinds((PERSON, 1), (AUCTION, 2), (PERSON, 1), (AUCTION, 1))
    return _events(kinds, [900, 1100, 1200, 1300, 1400],
                   persons=[7, 0, 0, 8, 0], sellers=[0, 7, 7, 0, 8])


def _irregular_times():
    rows = _nexmark(5000, seed=5)
    rows["ts"] = np.sort(np.random.default_rng(5).integers(0, 7 * WIN, 5000))
    return rows


STREAMS = {
    "both_sides": (_both_sides, 700),
    "window_without_person": (_window_without_person, 700),
    "window_without_auction": (_window_without_auction, 700),
    "hot_seller": (_hot_seller, 512),
    "person_after_its_auctions": (_person_after_its_auctions, 3),
    "seller_of_the_window_before": (_seller_of_the_window_before, 2),
    "chunks_straddle_a_window_end": (_both_sides, 333),
    "one_chunk": (_both_sides, 6000),
    "irregular_times": (_irregular_times, 257),
}


@pytest.mark.parametrize("case", sorted(STREAMS))
def test_join_equals_brute_force(case):
    make, chunk = STREAMS[case]
    rows = make()
    got, _pipe = _run(rows, chunk)
    want = oracle.join_windows(rows, WIN, **ORACLE)
    assert _table(got) == want
    if case not in ("seller_of_the_window_before",):
        assert len(want) > 1
    # results of window w leave before any of w + 1, in pieces
    wids = np.concatenate([b["id"] for b in got]) if got else np.zeros(0)
    assert (np.diff(wids) >= 0).all()
    assert all(len(b) <= JOIN["flush_rows"] for b in got)


def test_named_cases_hold_what_they_say():
    rows = _person_after_its_auctions()
    assert [r[:3] for r in oracle.join_windows(rows, WIN, **ORACLE)] == [
        (7, 0, 900), (7, 0, 900), (7, 0, 950)]
    rows = _seller_of_the_window_before()
    assert [r[:3] for r in oracle.join_windows(rows, WIN, **ORACLE)] == [
        (8, 1, 1400)]
    rows = _window_without_person()
    assert 1 not in {r[1] for r in oracle.join_windows(rows, WIN, **ORACLE)}


def test_fields_of_both_rows_are_carried():
    rows = _nexmark(3000, seed=9)
    got, _pipe = _run(rows, join=dict(left_fields=("city",)))
    want = oracle.join_windows(rows, WIN, left_fields=("city",), **ORACLE)
    assert _table(got, ("auction", "reserve", "city")) == want
    assert len({r[-1] for r in want}) > 10


def test_the_builder_builds_the_same_pattern():
    rows = _nexmark(3000, seed=2)
    built = (WinJoinTPU_Builder().withName("q8").withTBWindow(WIN, WIN)
             .withSides("event_type", left=(PERSON, "person"),
                        right=(AUCTION, "seller"))
             .withKeyRange(0, 1 << 20)
             .withFields(right=("auction", "reserve"),
                         ranges=JOIN["field_ranges"])
             .withWindowRows(2048).withMaxResults(1024).withFlushRows(256)
             .build())
    assert isinstance(built, WinJoinTPU) and built.name == "q8"
    got, pipe = _run(rows, pattern=built)
    assert _table(got) == oracle.join_windows(rows, WIN, **ORACLE)
    (core,) = window_cores(pipe._df)
    assert isinstance(core, WinJoinCore)
    assert type(core.executor).__name__ == "MultiFieldResidentExecutor"
    assert core.executor.KP == 1 and core.executor.mesh is None


# -- the result's slots --------------------------------------------------------

def _n_matches(n):
    """One person, then `n` auctions of it, in one window; a second window's
    person closes it."""
    kinds = _kinds((PERSON, 1), (AUCTION, n), (PERSON, 1))
    ts = np.concatenate([np.arange(n + 1) * (WIN - 1) // (n + 1), [WIN + 1]])
    return _events(kinds, ts, persons=[7] + [0] * n + [8], sellers=7)


def test_a_result_exactly_at_its_slots_is_whole():
    cap = _bucket_fine(1500)
    rows = _n_matches(cap)
    got, pipe = _run(rows, join=dict(max_results=1500, window_rows=4096))
    assert _table(got) == oracle.join_windows(rows, WIN, **ORACLE)
    assert sum(len(b) for b in got) == cap
    (core,) = window_cores(pipe._df)
    assert core.cap == cap and core.join_slots_filled == cap
    assert core.join_refused == 0


def test_a_result_one_past_its_slots_raises_and_is_never_cut():
    cap = _bucket_fine(1500)
    rows = _n_matches(cap + 1)
    with pytest.raises(ValueError, match=rf"joins {cap + 1} rows .* over "
                       rf"the {cap} slots .* max_results") as err:
        _run(rows, join=dict(max_results=1500, window_rows=4096))
    assert "never handed on" in str(err.value)


def test_a_window_over_window_rows_grows_and_stays_exact():
    rows = _nexmark(20000, step=1, seed=4)
    got, pipe = _run(rows, chunk=3000, join=dict(
        window_rows=64, max_results=1024, flush_rows=32))
    want = oracle.join_windows(rows, WIN, **ORACLE)
    assert _table(got) == want
    (core,) = window_cores(pipe._df)
    assert core.executor.cap >= 80          # the rings grew on the device


def test_a_duplicate_left_key_raises_by_name():
    kinds = _kinds((PERSON, 2), (AUCTION, 3), (PERSON, 1))
    rows = _events(kinds, [10, 20, 30, 40, 50, WIN + 5],
                   persons=[7, 7, 0, 0, 0, 9], sellers=7)
    with pytest.raises(KeyError):
        oracle.join_windows(rows, WIN, **ORACLE)
    with pytest.raises(ValueError, match="window 0 holds 1 left rows whose "
                       "key another left row .* many-to-many"):
        _run(rows)
    # ... and the same key in two windows is no duplicate
    kinds = _kinds((PERSON, 1), (AUCTION, 3), (PERSON, 1), (AUCTION, 1))
    rows = _events(kinds, [10, 30, 40, 50, WIN + 1, WIN + 5],
                   persons=[7, 0, 0, 0, 7, 0], sellers=7)
    got, _pipe = _run(rows)
    want = oracle.join_windows(rows, WIN, **ORACLE)
    assert _table(got) == want and [r[1] for r in want] == [0, 0, 0, 1]


def test_a_value_outside_its_declared_range_raises_by_name():
    rows = _nexmark(500)
    rows["reserve"][rows["event_type"] == AUCTION] = 1 << 29
    with pytest.raises(ValueError, match="carried field 'reserve' holds .* "
                       "outside its declared range"):
        _run(rows)
    rows = _nexmark(500)
    with pytest.raises(ValueError, match="the join key holds .* outside"):
        _run(rows, join=dict(key_range=(0, 1001)))


def test_late_rows_are_dropped_and_counted():
    rows = _nexmark(4000, seed=6)
    late = rows[100:150].copy()              # of window 0, behind window 2
    mixed = np.concatenate([rows[:2500], late, rows[2500:]])
    got, pipe = _run(mixed, chunk=500)
    assert _table(got) == oracle.join_windows(rows, WIN, **ORACLE)
    (core,) = window_cores(pipe._df)
    assert core.late_rows == int(np.count_nonzero(
        late["event_type"] != BID))


# -- what is refused, each by name ----------------------------------------------

REFUSED = {
    "sliding": (dict(slide_len=WIN // 2), "sliding or hopping window"),
    "hopping": (dict(slide_len=2 * WIN), "sliding or hopping window"),
    "count_based": (dict(win_type=WinType.CB), "count-based window"),
    "many_to_many": (dict(left_unique=False), "many-to-many join"),
    "stream_time": (dict(fire_on="stream"), "fire_on='stream'"),
    "timer": (dict(max_delay_ms=5), "max_delay_ms"),
    "mesh": (dict(mesh=object()), "a mesh"),
    "degree": (dict(pardegree=2), "a degree of 2"),
    "key_range_wide": (dict(key_range=(0, 1 << 40)),
                       "of the join key does not fit"),
    "key_range_missing": (dict(key_range=None),
                          "the join key needs a declared range"),
    "field_range_missing": (dict(field_ranges={}),
                            "'auction' needs a declared range"),
    "field_range_wide": (dict(field_ranges={"auction": (0, 1 << 33),
                                            "reserve": (0, 9)}),
                         "of the carried field 'auction' does not fit"),
    "same_side": (dict(right=(PERSON, "seller")), "two different sides"),
    "field_twice": (dict(left_fields=("auction",)), "must differ"),
    "field_named_ts": (dict(left_fields=("ts",)), "must differ"),
}


@pytest.mark.parametrize("form", sorted(REFUSED))
def test_refused_at_construction_by_name(form):
    extra, says = REFUSED[form]
    kw = dict(JOIN, **extra)
    with pytest.raises(ValueError) as err:
        WinJoinTPU(WIN, kw.pop("slide_len", None),
                   kw.pop("win_type", WinType.TB), name="refused", **kw)
    assert "WinJoinTPU 'refused'" in str(err.value)
    assert says in str(err.value)


def test_recovery_is_refused_when_the_graph_is_built():
    rows = _nexmark(500)
    with pytest.raises(ValueError, match="WinJoinTPU .* recovery= is not "
                       "supported"):
        _run(rows, recovery=RecoveryPolicy(epoch_period=0.05))
    from windflow_tpu.check import validate
    pipe = (MultiPipe("checked", recovery=RecoveryPolicy(epoch_period=0.05))
            .add_source(Source(batches=_chunks(rows, 100), schema=SCHEMA))
            .add(WinJoinTPU(WIN, **JOIN))
            .add_sink(Sink(lambda b: None, vectorized=True)))
    report = validate(pipe)
    assert [d.code for d in report.diagnostics] == ["WF218"]
    assert report.has_errors


def test_the_plan_is_the_python_resident_cores_multi_family():
    core = WinJoinTPU(WIN, **JOIN).make_core()
    plan = plan_core(WindowSpec(WIN, WIN, WinType.TB), core.fn,
                     use_resident=True)
    assert (plan.core, plan.family, plan.mesh) == ("resident_py", "multi",
                                                   False)
    assert core.fn.count_field == "matches"
    assert core.fn.slot_cap == core.cap == _bucket_fine(1024)
    assert core.fn.window_rows == 2048


# -- what the program records ---------------------------------------------------

def test_counters_spans_and_steps(tmp_path):
    rows = _nexmark(6000, seed=1)
    want = oracle.join_windows(rows, WIN, **ORACLE)
    profile.enable()
    profile.reset()
    got, pipe = _run(rows, join=dict(flush_rows=32), trace_dir=str(tmp_path))
    spans, counters = profile.report(), profile.counters()
    assert _table(got) == want
    n_left = int(np.count_nonzero(rows["event_type"] == PERSON))
    n_right = int(np.count_nonzero(rows["event_type"] == AUCTION))
    (core,) = window_cores(pipe._df)
    assert counters["join_windows"] == core.join_windows == 6
    assert counters["join_left_rows"] == core.join_left_rows == n_left
    assert counters["join_right_rows"] == core.join_right_rows == n_right
    assert counters["join_results"] == core.join_results == len(want)
    assert counters["join_slots_filled"] == len(want)
    assert counters["join_slots_asked"] == 6 * core.cap
    assert "join_refused" not in counters
    assert spans["join_stage"][1] > 0 and spans["join_unpack"][1] >= 6
    # the rows went in rectangles of flush_rows by the append-only step; the
    # step bound to the join ran once a window, at ONE padded length
    assert any(k.family == "append" and k.Rb == 32 and k.KP == 1
               for k in resident._STEP_CACHE)
    assert counters["udf_windows"] == 6
    assert counters["udf_cells"] == 6 * _bucket_fine(2048)
    # a window of 80 rows ships 64 of them ahead of its close
    assert core.executor.dispatches == 6 * 3
    assert spans["launch_take"][1] == spans["dispatch"][1] == 18
    assert spans["harvest_wait"][1] == 6
    log = json.load(open(next(
        os.path.join(tmp_path, f) for f in os.listdir(tmp_path)
        if "win_join" in f and f.endswith(".log"))))
    assert log["join_results"] == len(want) and log["windows_fired"] == 6
    assert log["join_slots_asked"] == 6 * core.cap


def test_a_second_pipeline_builds_no_step():
    rows = _nexmark(3000, seed=8)
    _run(rows)
    resident.stats_snapshot(reset=True)
    got, _pipe = _run(rows)
    assert resident.stats_snapshot()["udf_step_builds"] == 0
    assert _table(got) == oracle.join_windows(rows, WIN, **ORACLE)


def test_an_idle_worker_is_woken_for_its_result():
    # every chunk of a window, then nothing for a while: the result leaves
    # by the watcher's wake, not with the next chunk
    import time
    rows = _nexmark(2200, seed=7)
    want = oracle.join_windows(rows, WIN, **ORACLE)
    n_due = len([r for r in want if r[1] < 2])
    got, seen = [], []

    def batches():
        for b in _chunks(rows, 550):
            yield b
        # window 1 was closed by the last chunk: its result has to come
        # while the worker sits idle (a compile may stand in its way)
        deadline = time.monotonic() + 30.0
        while sum(len(g) for g in got) < n_due \
                and time.monotonic() < deadline:
            time.sleep(0.05)
        seen.append(sum(len(g) for g in got))

    pipe = (MultiPipe("woken")
            .add_source(Source(batches=batches(), schema=SCHEMA))
            .add(WinJoinTPU(WIN, **JOIN))
            .add_sink(Sink(lambda b: got.append(b.copy())
                           if b is not None and len(b) else None,
                           vectorized=True)))
    pipe.run_and_wait_end()
    assert _table(got) == want
    (core,) = window_cores(pipe._df)
    assert core.result_wakes >= 1
    assert seen[0] == n_due > 0


# -- the benchmark's configuration against its reference ------------------------

def test_the_benchmarks_configuration_equals_its_reference():
    with open(os.path.join(BENCH, "configs", "q8_new_users.json")) as f:
        cfg = json.load(f)
    cfg["shapes"].update(win_us=1000, slide_us=1000, flush_rows=512,
                         window_rows=4096, max_results=3072)
    cfg["stream"]["template_events"] = 5000
    chunk, seed = 1000, 11
    dtype = q8_new_users.record_dtype(cfg)
    bases = [0, 200, 400, 999, 1000, 1500, 2100, 2100, 3900, 4000, 4100, 5200]
    log = {"chunk": chunk, "base_us": np.asarray(bases, dtype=np.int64),
           "off_us": np.zeros(chunk, dtype=np.int64)}

    def source(shipper):
        period = q8_new_users_oracle.period_events(cfg)
        for j, base in enumerate(bases):
            cycle, phase = divmod(j * chunk, period)
            b = np.zeros(chunk, dtype=dtype)
            for name, col in q8_new_users_oracle.columns(
                    cfg, seed, phase, chunk).items():
                b[name] = col
            b["id"] += cycle * period
            b["ts"] = base
            shipper.push_batch(b)

    got = []
    q8_new_users.build(cfg, source, lambda b: got.append(b.copy())
                       if b is not None and len(b) else None
                       ).run_and_wait_end()
    rows = np.concatenate(got)
    table = {k: np.asarray(v, dtype=np.int64)
             for k, v in q8_new_users.result_table(rows).items()}
    want = q8_new_users_oracle.expected(cfg, seed, log)
    numbers, _ = check.compare(table, want)
    assert check.verdict(numbers)[0], numbers
    assert len(want["key"]) > 300 and len(np.unique(want["wid"])) == 6
    # a result is timed from its window's last contributing event
    last = q8_new_users.result_event_time_us(rows)
    for wid in np.unique(rows["id"]):
        of = rows["id"] == wid
        assert (last[of] == rows["ts"][of].max()).all()
