"""Differential tests: vectorised WinSeqCore vs the brute-force oracle.

Covers CB/TB x NIC/INC x sliding/tumbling/hopping x single/multi key x
chunk sizes x farm-worker PatternConfigs x EOS markers — the same invariant
matrix the reference exercises via src/sum_test_cpu (test_all_cb/tb.cpp).
"""

import numpy as np
import pytest

from windflow_tpu.core.tuples import Schema, batch_from_columns
from windflow_tpu.core.windows import PatternConfig, Role, WindowSpec, WinType
from windflow_tpu.core.winseq import WinSeqCore
from windflow_tpu.ops.functions import Reducer

from oracle import OracleWinSeq

SCHEMA = Schema(value=np.int64)


def feed(core, stream, chunk):
    """Feed `stream` (list of (key,id,ts,value[,marker])) in chunks: the
    result batch of every ``process`` call, then the flush's."""
    outs = []
    for i in range(0, len(stream), chunk):
        part = stream[i:i + chunk]
        b = batch_from_columns(
            SCHEMA,
            key=[r[0] for r in part], id=[r[1] for r in part],
            ts=[r[2] for r in part], value=[r[3] for r in part])
        b["marker"] = [len(r) > 4 and r[4] for r in part]
        outs.append(core.process(b))
    return outs, core.flush()


def run_core(core, stream, chunk):
    """Feed `stream` in chunks (``feed``); return per-key result lists."""
    outs, flushed = feed(core, stream, chunk)
    out = np.concatenate(outs + [flushed])
    per_key = {}
    for r in out:
        per_key.setdefault(int(r["key"]), []).append(
            (int(r["id"]), int(r["ts"]), int(r["value"])))
    return per_key


def run_oracle(oracle, stream):
    res = []
    for r in stream:
        marker = r[4] if len(r) > 4 else False
        res += oracle.push(r[0], r[1], r[2], marker=marker, value=r[3])
    res += oracle.eos()
    per_key = {}
    for r in res:
        per_key.setdefault(int(r["key"]), []).append(
            (int(r["id"]), int(r["ts"]), int(r["value"])))
    return per_key


def nic_sum(key, gwid, rows):
    return sum(r["value"] for r in rows)


def inc_sum(key, gwid, row, acc):
    if row is None:
        return 0
    return acc + row["value"]


def make_cb_stream(keys, n, seed=0, interleave=True):
    """Deterministic integer stream like the reference sum_cb Generator:
    ids 0..n-1 per key, value = id (sum_cb.hpp:105-110)."""
    rng = np.random.default_rng(seed)
    stream = []
    if interleave:
        for i in range(n):
            for k in range(keys):
                stream.append((k, i, i * 10 + int(rng.integers(0, 10)), i))
    else:
        for k in range(keys):
            for i in range(n):
                stream.append((k, i, i * 10, i))
    return stream


def make_tb_stream(keys, n, seed=0, max_gap=30):
    """Time-based stream with irregular (possibly gapping/duplicate) ts."""
    rng = np.random.default_rng(seed)
    stream = []
    for k in range(keys):
        ts = 0
        for i in range(n):
            ts += int(rng.integers(0, max_gap))
            stream.append((k, i, ts, i))
    stream.sort(key=lambda r: (r[2], r[0]))
    return stream


CASES = [
    # (win, slide) sliding / tumbling / hopping
    (8, 3), (8, 8), (3, 8), (5, 1), (1, 1), (16, 7),
]


@pytest.mark.parametrize("win,slide", CASES)
@pytest.mark.parametrize("chunk", [1, 7, 1000])
@pytest.mark.parametrize("keys", [1, 3])
def test_cb_nic_matches_oracle(win, slide, chunk, keys):
    stream = make_cb_stream(keys, 100)
    spec = WindowSpec(win, slide, WinType.CB)
    core = WinSeqCore(spec, Reducer("sum"))
    oracle = OracleWinSeq(win, slide, "CB", nic_sum, True)
    assert run_core(core, stream, chunk) == run_oracle(oracle, stream)


@pytest.mark.parametrize("win,slide", CASES)
@pytest.mark.parametrize("chunk", [1, 7, 1000])
def test_cb_inc_matches_oracle(win, slide, chunk):
    stream = make_cb_stream(2, 80)
    spec = WindowSpec(win, slide, WinType.CB)
    core = WinSeqCore(spec, Reducer("sum")).use_incremental()
    oracle = OracleWinSeq(win, slide, "CB", inc_sum, False)
    assert run_core(core, stream, chunk) == run_oracle(oracle, stream)


@pytest.mark.parametrize("win,slide", [(50, 20), (40, 40), (20, 50), (100, 7)])
@pytest.mark.parametrize("chunk", [1, 13, 1000])
@pytest.mark.parametrize("nic", [True, False])
def test_tb_matches_oracle(win, slide, chunk, nic):
    stream = make_tb_stream(2, 120)
    spec = WindowSpec(win, slide, WinType.TB)
    core = WinSeqCore(spec, Reducer("sum"))
    if not nic:
        core.use_incremental()
    oracle = OracleWinSeq(win, slide, "TB", nic_sum if nic else inc_sum, nic)
    assert run_core(core, stream, chunk) == run_oracle(oracle, stream)


@pytest.mark.parametrize("op", ["sum", "count", "min", "max"])
def test_reducers_match_oracle(op):
    stream = make_cb_stream(2, 60, seed=3)
    spec = WindowSpec(10, 4, WinType.CB)
    core = WinSeqCore(spec, Reducer(op))

    def nic(key, gwid, rows):
        vals = [r["value"] for r in rows]
        if op == "sum":
            return sum(vals)
        if op == "count":
            return len(vals)
        if op == "min":
            return min(vals) if vals else np.iinfo(np.int64).max
        return max(vals) if vals else np.iinfo(np.int64).min

    oracle = OracleWinSeq(10, 4, "CB", nic, True)
    assert run_core(core, stream, 17) == run_oracle(oracle, stream)


@pytest.mark.parametrize("role,cfg_t", [
    # farm-worker configs: (id_outer, n_outer, slide_outer, id_inner, n_inner, slide_inner)
    (Role.SEQ, (1, 4, 3, 0, 1, 3)),    # Win_Farm worker 1 of 4 (private slide)
    (Role.SEQ, (3, 4, 3, 0, 1, 3)),
    (Role.PLQ, (0, 1, 2, 1, 3, 2)),    # Pane_Farm PLQ worker
    (Role.WLQ, (0, 1, 4, 2, 4, 4)),    # Pane_Farm WLQ worker
    (Role.MAP, (0, 1, 3, 0, 1, 3)),
])
@pytest.mark.parametrize("chunk", [1, 11, 1000])
def test_pattern_config_roles_match_oracle(role, cfg_t, chunk):
    win, slide = 6, 3
    if role is Role.SEQ:
        # Win_Farm worker: window wid of worker i covers the same ids as
        # global window gwid; private slide = slide * n_outer
        slide_eff = cfg_t[2] * cfg_t[1]
    else:
        slide_eff = slide
    stream = make_cb_stream(3, 90, seed=7)
    spec = WindowSpec(win, slide_eff if role is Role.SEQ else slide, WinType.CB)
    cfg = PatternConfig(*cfg_t)
    mi = (1, 3) if role is Role.MAP else (0, 1)
    core = WinSeqCore(spec, Reducer("sum"), config=cfg, role=role, map_indexes=mi)
    oracle = OracleWinSeq(spec.win_len, spec.slide_len, "CB", nic_sum, True,
                          config=cfg_t, role=role.name, map_indexes=mi)
    assert run_core(core, stream, chunk) == run_oracle(oracle, stream)


@pytest.mark.parametrize("chunk", [1, 9, 1000])
def test_markers_match_oracle(chunk):
    """EOS markers (the last real tuple replayed with marker=True) open and
    fire trailing windows without contributing values."""
    base = make_cb_stream(2, 40)
    # append a marker per key replaying its last tuple
    last = {}
    for r in base:
        last[r[0]] = r
    stream = base + [(k, r[1], r[2], r[3], True) for k, r in sorted(last.items())]
    spec = WindowSpec(7, 2, WinType.CB)
    core = WinSeqCore(spec, Reducer("sum"))
    oracle = OracleWinSeq(7, 2, "CB", nic_sum, True)
    assert run_core(core, stream, chunk) == run_oracle(oracle, stream)


def test_out_of_order_dropped():
    spec = WindowSpec(4, 2, WinType.CB)
    core = WinSeqCore(spec, Reducer("sum"))
    stream = [(0, 0, 0, 0), (0, 1, 1, 1), (0, 5, 5, 5), (0, 2, 2, 2),
              (0, 6, 6, 6), (0, 7, 7, 7)]
    oracle = OracleWinSeq(4, 2, "CB", nic_sum, True)
    assert run_core(core, stream, 3) == run_oracle(oracle, stream)


def test_duplicate_positions():
    spec = WindowSpec(5, 5, WinType.TB)
    core = WinSeqCore(spec, Reducer("sum"))
    stream = [(0, 0, 1, 1), (0, 1, 1, 2), (0, 2, 3, 3), (0, 3, 3, 4),
              (0, 4, 7, 5), (0, 5, 12, 6)]
    oracle = OracleWinSeq(5, 5, "TB", nic_sum, True)
    assert run_core(core, stream, 2) == run_oracle(oracle, stream)


@pytest.mark.parametrize("seed", range(8))
@pytest.mark.parametrize("nic", [True, False])
def test_fuzz_sparse_streams(seed, nic):
    """Sparse/gapping id streams (empty windows, id jumps) vs the oracle —
    the dense-stream cases never exercise empty CB windows."""
    rng = np.random.default_rng(seed)
    win = int(rng.integers(1, 12))
    slide = int(rng.integers(1, 12))
    keys = int(rng.integers(1, 4))
    wt = WinType.CB if seed % 2 == 0 else WinType.TB
    stream = []
    for k in range(keys):
        pos = 0
        for i in range(60):
            pos += int(rng.integers(0, 9))  # gaps and duplicates
            if wt is WinType.CB:
                stream.append((k, pos, int(rng.integers(0, 1000)), i))
            else:
                stream.append((k, i, pos, i))
    rng.shuffle(stream)  # interleave keys; per-key order is preserved by sort
    stream.sort(key=lambda r: (r[1] if wt is WinType.CB else r[2]))
    spec = WindowSpec(win, slide, wt)
    core = WinSeqCore(spec, Reducer("sum"))
    if not nic:
        core.use_incremental()
    oracle = OracleWinSeq(win, slide, wt.name, nic_sum if nic else inc_sum, nic)
    chunk = int(rng.integers(1, 40))
    assert run_core(core, stream, chunk) == run_oracle(oracle, stream)


def test_sum_invariant_totals():
    """The reference's headline invariant: total sum over all windows is
    identical however the stream is chunked (test_all_cb.cpp:171+)."""
    stream = make_cb_stream(4, 200)
    totals = set()
    for chunk in (1, 3, 64, 10000):
        spec = WindowSpec(10, 5, WinType.CB)
        core = WinSeqCore(spec, Reducer("sum"))
        per_key = run_core(core, stream, chunk)
        totals.add(sum(v for rs in per_key.values() for _, _, v in rs))
        # per-key results arrive in wid order 0,1,2,... (Consumer check,
        # sum_cb.hpp:146-150)
        for rs in per_key.values():
            assert [r[0] for r in rs] == list(range(len(rs)))
    assert len(totals) == 1


# ------------------------------------------------------------------------
# dense_positions: a stage the library wired over its own pane stream (a
# Pane_Farm's WLQ) fires a window with the row at its LAST position, not
# with the first one behind it.  Same rows; only the call that returns them
# moves.

def by_key(batches):
    """Rows of ``batches`` in arrival order, grouped by key (stable): which
    key's window leaves first inside one chunk is not part of the result."""
    out = np.concatenate(batches)
    return out[np.argsort(out["key"], kind="stable")]


def dense_pair(spec, nic, **kw):
    cores = [WinSeqCore(spec, Reducer("sum"), dense_positions=d, **kw)
             for d in (True, False)]
    if not nic:
        for c in cores:
            c.use_incremental()
    return cores


DENSE_GEOMETRY = [(20, 1), (6, 3), (4, 4)]


@pytest.mark.parametrize("keys", [1, 3])
@pytest.mark.parametrize("win,slide", DENSE_GEOMETRY)
@pytest.mark.parametrize("nic", [True, False], ids=["nic", "inc"])
@pytest.mark.parametrize("chunk", [1, 7, 1000])
def test_dense_positions_fire_a_window_with_its_last_row(chunk, nic, win,
                                                         slide, keys):
    n = 60
    stream = make_cb_stream(keys, n)
    on, off = dense_pair(WindowSpec(win, slide, WinType.CB), nic)
    on_outs, on_flush = feed(on, stream, chunk)
    off_outs, off_flush = feed(off, stream, chunk)
    # the same rows, in the same order a key
    assert np.array_equal(by_key(on_outs + [on_flush]),
                          by_key(off_outs + [off_flush]))
    # the flush is left with the windows the stream's end cut short only
    assert all(g * slide + win > n for g in on_flush["id"])
    fired = sum(len(o) for o in on_outs)
    assert fired == sum((n - win) // slide + 1 for _k in range(keys)) > 0
    assert on.windows_fired_complete == fired
    assert off.windows_fired_complete is None
    if chunk == 1:
        # window w leaves with the call that carries id end - 1; under
        # the reference's rule with the call that carries id end
        for (key, i, _ts, _v), got, ref in zip(stream, on_outs, off_outs):
            last = [g for g in range(n) if g * slide + win - 1 == i]
            nxt = [g for g in range(n) if g * slide + win <= i
                   and g * slide + win > i - 1]
            assert got["id"].tolist() == last and set(got["key"]) <= {key}
            assert ref["id"].tolist() == nxt


def worker_stream(stream, cfg, spec):
    """What a Win_Farm emitter hands the worker of ``cfg``: every id of each
    of its windows, and at the end a marker copy of each key's last row."""
    kept = []
    for r in stream:
        rel = r[1] - cfg.initial_id(r[0], Role.WLQ)
        if rel >= 0 and bool(spec.in_any_window(rel)):
            kept.append(r)
    last = {}
    for r in stream:
        last[r[0]] = r
    return kept + [r[:4] + (True,) for _k, r in sorted(last.items())]


@pytest.mark.parametrize("win,slide,n_workers", [
    (20, 1, 3),     # private slide 3: sliding
    (6, 3, 2),      # private slide 6: tumbling
    (6, 3, 3),      # private slide 9: hopping, ids between its windows
])
@pytest.mark.parametrize("nic", [True, False], ids=["nic", "inc"])
@pytest.mark.parametrize("chunk", [1, 7, 1000])
def test_dense_positions_on_a_wlq_farm_worker_with_eos_markers(
        chunk, nic, win, slide, n_workers):
    stream = make_cb_stream(3, 60, seed=5)
    spec = WindowSpec(win, slide * n_workers, WinType.CB)
    early = 0
    for i in range(n_workers):
        cfg = PatternConfig(0, 1, slide, i, n_workers, slide)
        on, off = dense_pair(spec, nic, config=cfg, role=Role.WLQ)
        mine = worker_stream(stream, cfg, spec)
        on_outs, on_flush = feed(on, mine, chunk)
        off_outs, off_flush = feed(off, mine, chunk)
        # ids, ts (a marker overwrites the ts of a window it falls below,
        # window.hpp:149-154) and values: equal
        assert np.array_equal(by_key(on_outs + [on_flush]),
                              by_key(off_outs + [off_flush]))
        assert len(on_flush) <= len(off_flush)
        early += len(off_flush) - len(on_flush)
        assert on.windows_fired_complete == sum(len(o) for o in on_outs)
    # some worker's window ends with the stream's last id: the reference's
    # rule left it to the flush, under the marker's ts
    assert early > 0


@pytest.mark.parametrize("chunk", [1, 1000])
def test_a_window_fired_with_its_last_row_has_the_ts_the_marker_gave_it(
        chunk):
    # ids 0..11, windows of 6 every 3: window 2 is [6, 12), its last id the
    # stream's last.  The reference's rule never fires it: the marker (a
    # copy of row 11) is not past its end, so the flush emits it with the
    # marker's ts.  Fired with row 11 it carries row 11's own: the same.
    stream = [(0, i, 100 + 7 * i, i) for i in range(12)]
    stream.append(stream[-1] + (True,))
    on, off = dense_pair(WindowSpec(6, 3, WinType.CB), True)
    on_outs, on_flush = feed(on, stream, chunk)
    off_outs, off_flush = feed(off, stream, chunk)
    assert np.array_equal(np.concatenate(on_outs + [on_flush]),
                          np.concatenate(off_outs + [off_flush]))
    got = np.concatenate(on_outs)
    assert got["id"].tolist() == [0, 1, 2]
    assert got["ts"].tolist() == [100 + 7 * 5, 100 + 7 * 8, 100 + 7 * 11]
    assert 2 in off_flush["id"] and 2 not in on_flush["id"]


def test_a_wlq_core_over_a_users_stream_keeps_the_next_id_trigger():
    """Why the property is handed down by the pattern and never read off
    ``Role.WLQ``: a stream that is not the library's own may repeat an id,
    and the row that repeats it belongs to the window the first one ends."""
    stream = [(0, 0, 0, 1), (0, 1, 10, 2), (0, 2, 20, 4), (0, 3, 30, 8),
              (0, 3, 31, 16), (0, 4, 40, 32), (0, 5, 50, 64)]
    spec = WindowSpec(4, 4, WinType.CB)
    cfg = (0, 1, 4, 0, 1, 4)
    core = WinSeqCore(spec, Reducer("sum"), config=PatternConfig(*cfg),
                      role=Role.WLQ)
    assert core.dense_positions is False
    oracle = OracleWinSeq(4, 4, "CB", nic_sum, True, config=cfg, role="WLQ")
    got = run_core(core, stream, 1)
    assert got == run_oracle(oracle, stream)
    assert got[0][0] == (0, 31, 1 + 2 + 4 + 8 + 16)
    # told what is not true of this stream, the core closes window 0 on
    # the first id 3 and the second one's value is in no window
    wrong = WinSeqCore(spec, Reducer("sum"), config=PatternConfig(*cfg),
                       role=Role.WLQ, dense_positions=True)
    assert run_core(wrong, stream, 1)[0][0] == (0, 30, 1 + 2 + 4 + 8)


def test_dense_positions_are_a_count_based_keyed_stages():
    with pytest.raises(ValueError, match="dense_positions"):
        WinSeqCore(WindowSpec(10, 5, WinType.TB), Reducer("sum"),
                   dense_positions=True)
    with pytest.raises(ValueError, match="dense_positions"):
        WinSeqCore(WindowSpec(10, 5, WinType.TB), Reducer("sum"),
                   fire_on="stream", dense_positions=True)
