"""Events that arrive late or out of order, kept: ``fire_on="stream"`` with a
hold-back (``holdback=``).  The stage's watermark is its clock less the
hold-back; a row is folded into every window of its key the watermark has
not closed, whatever rows of its key came before it, and is late only if all
of them are closed.  Every core that honours it -- ``WinSeqCore`` (NIC and
INC), ``VecStreamCore``, the native resident core, a ``KeyFarmTPU`` through
``MultiPipe`` -- against the loop ``oracle.watermark_windows``."""

import hashlib
import json
import warnings

import numpy as np
import pytest

from oracle import watermark_windows
from test_stream_fire import SPECS, busy_stream, quiet_stream, run_core
from windflow_tpu.api import MultiPipe
from windflow_tpu.api.builders import (KeyFarm_Builder, KeyFarmTPU_Builder,
                                       WinSeq_Builder)
from windflow_tpu.core.tuples import (MARKER_FIELD, Schema,
                                      batch_from_columns, progress_row)
from windflow_tpu.core.vecinc import VecStreamCore, make_vec_core
from windflow_tpu.core.windows import WindowSpec, WinType
from windflow_tpu.core.winseq import WinSeqCore
from windflow_tpu.native import load
from windflow_tpu.ops.functions import MultiReducer, Reducer
from windflow_tpu.patterns.basic import Sink, Source
from windflow_tpu.patterns.native_core import NativeResidentCore
from windflow_tpu.patterns.win_seq import window_cores
from windflow_tpu.patterns.win_seq_tpu import KeyFarmTPU, make_core_for

VALUE = Schema(value=np.int64)
R = dict(value_range=(0, 100))
#: (win, slide, the bound of the streams' disorder)
L, S, DELAY = 100, 25, 300
CORES = ["nic", "inc", "vec", "native"]


def late_stream(seed, n=12000, keys=11, span=4000, delay=DELAY, share=0.1,
                before_zero=True):
    """Rows in time order of which one in ten arrives up to ``delay`` behind
    its place; with ``before_zero`` some reach behind time 0."""
    rng = np.random.default_rng(seed)
    ts = np.sort(rng.integers(0, span, n))
    held = rng.random(n) < share
    ts = np.where(held, ts - rng.integers(1, delay + 1, n), ts)
    if not before_zero:
        ts = np.maximum(ts, 0)
    return batch_from_columns(VALUE, key=rng.integers(0, keys, n),
                              id=np.arange(n), ts=ts,
                              value=rng.integers(0, 100, n))


def in_order(batch):
    return batch[np.argsort(batch["ts"], kind="stable")]


def make_core(kind, win, slide, holdback, fire_on="stream"):
    spec = WindowSpec(win, slide, WinType.TB)
    stream = {"holdback": holdback} if fire_on == "stream" else {}
    if kind == "vec":
        return make_vec_core(spec, Reducer("sum"), fire_on=fire_on, **stream)
    if kind == "native":
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")     # int32 accumulate, declared
            return make_core_for(spec, Reducer("sum", **R), fire_on=fire_on,
                                 batch_len=64, flush_rows=2048, **stream)
    core = WinSeqCore(spec, Reducer("sum"), fire_on=fire_on, **stream)
    return core.use_incremental() if kind == "inc" else core


def sums(rows):
    real = rows[~rows[MARKER_FIELD]]
    out = {(int(r["key"]), int(r["id"])): int(r["value"]) for r in real}
    assert len(out) == len(real)              # each (key, window) once
    return out


def run(core, batch, seed=None, chunk=None):
    """Feed in chunks of random sizes (``seed``) or of ``chunk`` rows."""
    if chunk is None:
        during, flushed = run_core(core, batch, seed)
    else:
        during = np.concatenate([core.process(batch[lo:lo + chunk])
                                 for lo in range(0, len(batch), chunk)])
        flushed = core.flush()
    return during, flushed


# ------------------------------------------------- hold-back >= the disorder

@pytest.mark.parametrize("chunk", [97, 1500, 12000])
@pytest.mark.parametrize("kind", CORES)
def test_with_the_disorder_covered_no_row_is_late(kind, chunk):
    """The results are those of the same rows in order, whatever the
    chunking; windows before time 0 exist where rows lie there."""
    batch = late_stream(1)
    core = make_core(kind, L, S, DELAY)
    during, flushed = run(core, batch, chunk=chunk)
    want, late = watermark_windows(batch, L, S, DELAY)
    out = np.concatenate((during, flushed))
    assert not late and core.late_rows == 0
    assert sums(out) == want
    assert want == watermark_windows(in_order(batch), L, S, DELAY)[0]
    assert min(w for _k, w in want) < 0
    # what the watermark closed left in window order, progress rows too (the
    # native core's results come back from the device when they are done,
    # the last of them with the flush), and a progress row promises no more
    # than the watermark
    marks = np.flatnonzero(out[MARKER_FIELD])
    assert len(marks) > 20       # (launches merge, and their fires)
    assert (np.diff(out["ts"][:marks[-1] + 1]) >= 0).all()
    assert out["ts"][marks].max() <= batch["ts"].max() - DELAY


@pytest.mark.parametrize("win,slide", SPECS + [(90, 25)])
@pytest.mark.parametrize("kind", CORES)
def test_every_window_shape(kind, win, slide):
    batch = late_stream(2, span=600, delay=40, n=4000, before_zero=False)
    core = make_core(kind, win, slide, 40)
    got = sums(np.concatenate(run(core, batch, seed=5)))
    want, late = watermark_windows(batch, win, slide, 40)
    assert got == want and not late and core.late_rows == 0


@pytest.mark.parametrize("kind", ["nic", "inc"])
def test_hopping_windows_stay_on_the_host_cores(kind):
    batch = late_stream(3, span=600, delay=40, n=4000)
    core = make_core(kind, 4, 9, 40)
    got = sums(np.concatenate(run(core, batch, seed=5)))
    want = {}
    for r in batch:                     # a row in a gap lies in no window
        if int(r["ts"]) % 9 < 4:
            k = (int(r["key"]), int(r["ts"]) // 9)
            want[k] = want.get(k, 0) + int(r["value"])
    assert got == want and core.late_rows == 0


def test_the_native_core_counts_what_it_holds_back():
    batch = late_stream(4)
    core = make_core("native", L, S, DELAY)
    assert type(core) is NativeResidentCore and core.fire_on == "stream"
    run(core, batch, chunk=800)
    ts = batch["ts"]
    behind = 0
    for k in np.unique(batch["key"]):
        t = ts[batch["key"] == k]
        behind += int((t < np.maximum.accumulate(t)).sum())
    assert core.rows_out_of_order == behind > len(batch) // 20
    assert core.late_rows == 0 and core.rows_reinserted == 0
    assert 0 < core.rows_held_peak < len(batch)
    assert core.watermark_fires > 100
    assert core.keys_live == len(np.unique(batch["key"]))


# -------------------------------------------------- hold-back < the disorder

@pytest.mark.parametrize("holdback", [0, 10, 60, 150])
@pytest.mark.parametrize("kind", CORES)
def test_below_the_disorder_the_late_rows_are_the_references(kind, holdback):
    """A single worker, fixed batches: a row is late exactly when every
    window of its was closed as it arrived, and is counted; one whose windows
    have partly closed is folded into the open ones only."""
    batch = late_stream(6)
    core = make_core(kind, L, S, holdback)
    got = sums(np.concatenate(run(core, batch, chunk=700)))
    want, late = watermark_windows(batch, L, S, holdback)
    assert got == want
    assert core.late_rows == len(late) > 0
    assert want != watermark_windows(in_order(batch), L, S, holdback)[0]


@pytest.mark.parametrize("kind", CORES)
def test_a_row_whose_windows_have_partly_closed(kind):
    """Windows of 100 every 25, hold-back 10.  The clock reaches 1160, the
    watermark 1150: the windows that end at or before it, up to 42, are
    closed.  The row at 1040 lies in 38-41 and counts in none: late.  The row
    at 1090 lies in 40-43 and counts in 43 alone."""
    rows = batch_from_columns(
        VALUE, key=[1, 1, 1, 1, 1], id=np.arange(5),
        ts=[1000, 1160, 1040, 1090, 1300], value=[1, 2, 4, 8, 16])
    core = make_core(kind, 100, 25, 10)
    got = sums(np.concatenate((core.process(rows[:2]), core.process(rows[2:]),
                               core.flush())))
    want = {(1, w): 1 for w in range(37, 41)}
    want.update({(1, 43): 8 + 2, (1, 44): 2, (1, 45): 2, (1, 46): 2})
    want.update({(1, w): 16 for w in range(49, 53)})
    assert got == want == watermark_windows(rows, 100, 25, 10)[0]
    assert core.late_rows == 1
    if kind == "native":
        assert core.rows_reinserted == 1      # behind rows already shipped


# ------------------------------------------------- what must not have moved

#: sha256 (first 16 hex digits) of what the host cores gave on the in-order
#: streams of tests/test_stream_fire.py at the commit before the hold-back
#: came (646373f), over SPECS, processing and flush: ``holdback=0`` is
#: exactly that ``fire_on="stream"``
TODAY = {("vec", "quiet"): "13351f8ec2468004",
         ("vec", "busy"): "5474f03f2287a936",
         ("nic", "quiet"): "585f07979bc1ad39",
         ("nic", "busy"): "060cc6697b415fcb",
         ("inc", "quiet"): "585f07979bc1ad39",
         ("inc", "busy"): "060cc6697b415fcb"}


@pytest.mark.parametrize("kind,name", sorted(TODAY))
def test_without_a_hold_back_an_in_order_stream_gives_todays_bytes(kind,
                                                                   name):
    batch = quiet_stream(7) if name == "quiet" else busy_stream(3)
    digest = hashlib.sha256()
    for win, slide in SPECS:
        core = make_core(kind, win, slide, 0)
        for part in run_core(core, batch, 11):
            digest.update(part.tobytes())
        assert core.late_rows == 0
    assert digest.hexdigest()[:16] == TODAY[(kind, name)]


@pytest.mark.parametrize("kind", ["nic", "native"])
def test_on_the_keys_own_time_a_row_behind_its_keys_newest_is_dropped(kind):
    """``fire_on="key"`` keeps the reference's rule (win_seq.hpp:293-305):
    the results are those of the stream without such rows."""
    batch = late_stream(7, before_zero=False)
    kept = np.ones(len(batch), dtype=bool)
    for k in np.unique(batch["key"]):
        at = np.flatnonzero(batch["key"] == k)
        t = batch["ts"][at]
        kept[at] = t >= np.maximum.accumulate(t)
    assert 500 < (~kept).sum()
    got = [sums(np.concatenate(run(make_core(kind, L, S, 0, fire_on="key"),
                                   rows, chunk=900)))
           for rows in (batch, batch[kept])]
    assert got[0] == got[1]
    # ... which are short of the stream's own sums
    whole = {}
    for r in batch:
        for w in range(max((int(r["ts"]) - L) // S + 1, 0),
                       int(r["ts"]) // S + 1):
            whole[(int(r["key"]), w)] = whole.get(
                (int(r["key"]), w), 0) + int(r["value"])
    assert sum(got[0].values()) < sum(whole.values())


def test_the_bulk_path_still_takes_a_key_periodic_stream_whole():
    """``sum_cb``'s stream (event i has key i % 64, ids in order) never
    meets the general loop, where the hold-back lives."""
    n, keys = 1 << 16, 64
    i = np.arange(n)
    batch = batch_from_columns(VALUE, key=i % keys, id=i // keys, ts=i,
                               value=i % 100)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        core = make_core_for(WindowSpec(256, 64, WinType.CB),
                             Reducer("sum", **R), batch_len=1024,
                             flush_rows=8192)
    assert type(core) is NativeResidentCore
    for lo in range(0, n, 1 << 14):
        core.process(batch[lo:lo + (1 << 14)])
    assert load().wf_core_fast_rows(core._hs[0]) == n
    core.flush()


# ---------------------------------------------------------- through MultiPipe

def run_pipe(stage, batch, chunk, trace_dir=None):
    got = []

    def source(shipper):
        for lo in range(0, len(batch), chunk):
            shipper.push_batch(batch[lo:lo + chunk].copy())

    pipe = (MultiPipe("late", trace_dir=trace_dir)
            .add_source(Source(source, VALUE, name="src"))
            .add(stage)
            .add_sink(Sink(lambda r: got.append(r.copy())
                           if r is not None else None, vectorized=True)))
    pipe.run_and_wait_end(timeout=120)
    out = np.concatenate(got)
    assert not out[MARKER_FIELD].any()          # a sink sees no marker row
    return pipe, out


@pytest.mark.parametrize("chunk", [211, 2000, 12000])
@pytest.mark.parametrize("pardegree", [1, 2, 4])
def test_key_farm_tpu_through_multipipe(pardegree, chunk):
    """Independent of arrival order, chunking and pardegree; every worker on
    the native resident core; a key's results in window order."""
    batch = late_stream(8, keys=23)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        stage = (KeyFarmTPU_Builder(Reducer("sum", **R))
                 .withTBWindow(L, S).withParallelism(pardegree)
                 .withBatch(64).withFlushRows(4096).withStreamTime(DELAY)
                 .withName("kf").build())
        pipe, out = run_pipe(stage, batch, chunk)
    assert [type(c) for c in window_cores(pipe._df)] == \
        [NativeResidentCore] * pardegree
    assert sums(out) == watermark_windows(in_order(batch), L, S, DELAY)[0]
    for k in np.unique(out["key"]):
        assert (np.diff(out["id"][out["key"] == k]) > 0).all()
    if pardegree > 1:
        # behind the farm's merge the workers' windows interleave in order
        assert (np.diff(out["ts"]) >= 0).all()


@pytest.mark.parametrize("builder", [KeyFarm_Builder, WinSeq_Builder])
def test_the_host_patterns_take_the_hold_back_too(builder):
    batch = late_stream(9)
    b = builder(Reducer("sum")).withTBWindow(L, S).withStreamTime(DELAY)
    if builder is KeyFarm_Builder:
        b = b.withParallelism(3)
    pipe, out = run_pipe(b.withName("host").build(), batch, 1000)
    assert {type(c) for c in window_cores(pipe._df)} == {VecStreamCore}
    assert sums(out) == watermark_windows(batch, L, S, DELAY)[0]


def test_multi_field_sums_on_the_native_core():
    """Two sums and a count in one window function: the ``multi`` family."""
    rng = np.random.default_rng(10)
    base = late_stream(10)
    schema = Schema(a=np.int64, b=np.int64)
    batch = batch_from_columns(schema, key=base["key"], id=base["id"],
                               ts=base["ts"], a=base["value"],
                               b=rng.integers(0, 50, len(base)))
    fn = MultiReducer(Reducer("sum", "a", "sa", **R),
                      Reducer("max", "b", "mb", **R),
                      Reducer("count", out_field="n"))
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        core = make_core_for(WindowSpec(L, S, WinType.TB), fn,
                             fire_on="stream", holdback=DELAY, batch_len=64,
                             flush_rows=2048)
    assert type(core) is NativeResidentCore
    rows = np.concatenate(run(core, batch, seed=3))
    rows = rows[~rows[MARKER_FIELD]]
    got = {(int(r["key"]), int(r["id"])): (int(r["sa"]), int(r["mb"]),
                                           int(r["n"])) for r in rows}
    want = {}
    for r in batch:
        t = int(r["ts"])
        for w in range((t - L) // S + 1, t // S + 1):
            a, b, n = want.get((int(r["key"]), w), (0, -1, 0))
            want[(int(r["key"]), w)] = (a + int(r["a"]),
                                        max(b, int(r["b"])), n + 1)
    assert got == want


def test_a_marker_row_moves_the_native_cores_clock():
    core = make_core("native", 10, 5, 3)
    rows = batch_from_columns(VALUE, key=[1, 2], id=[0, 1], ts=[102, 103],
                              value=[5, 6])
    core.process(rows)                          # windows 19 and 20
    out = core.process(progress_row(rows.dtype, 0, 107))    # watermark 104
    assert not len(out[~out[MARKER_FIELD]])
    out = core.process(progress_row(rows.dtype, 0, 108))    # 105: 19 closes
    assert sums(out) == {(1, 19): 5, (2, 19): 6}
    assert out[MARKER_FIELD].tolist() == [False, False, True]
    assert out["id"][-1] == 19 and out["ts"][-1] == 105
    assert sums(core.flush()) == {(1, 20): 5, (2, 20): 6}


def test_the_workers_report_what_they_held_and_dropped(tmp_path):
    batch = late_stream(11, keys=23)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        stage = KeyFarmTPU(Reducer("sum", **R), L, S, WinType.TB, pardegree=2,
                           batch_len=64, flush_rows=4096, fire_on="stream",
                           holdback=60, name="kf")
        run_pipe(stage, batch, 1500, trace_dir=str(tmp_path))
    logs = {}
    for path in tmp_path.glob("*.log"):
        node = json.loads(path.read_text())
        logs[node["node"].split("_", 2)[2]] = node
    workers = [logs[f"kf.{i}"] for i in range(2)]
    for w in workers:
        assert w["rows_out_of_order"] > 100 and w["late_rows"] > 0
        assert w["rows_held_peak"] > 0 and w["watermark_fires"] > 50
        assert 0 < w["progress_sent"] <= w["watermark_fires"]
    # a worker's clock is its own, so its late rows are those of its rows
    late = 0
    for i in range(2):
        mine = batch[batch["key"] % 2 == i]
        late += len(watermark_windows(mine, L, S, 60)[1])
    assert sum(w["late_rows"] for w in workers) == late
