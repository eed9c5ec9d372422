"""The ``spatial_pf`` reference: its windows by bisection and per-pane
skylines (``expected``) against the laid-out event times and every pair of a
whole window's points (``brute_force``); the bisection itself; the result
time of a window the stream's end leaves open; which windows are due; the
stream shared with ``spatial_wf``; the note on pane frontiers; and the
float16 control."""

import numpy as np
import pytest

from conftest import load
from configs import spatial_pf_oracle as oracle
from configs import spatial_wf_oracle
from harness import check, generator


def _small(grid_side=65536, win_us=2_000, slide_us=500):
    cfg = load("configs", "spatial_pf.json")
    cfg["stream"]["template_events"] = 2048
    cfg["shapes"].update(grid_side=grid_side, win_us=win_us,
                         slide_us=slide_us)
    return cfg


def _log(chunk, bases, rate=None):
    return {"chunk": chunk, "base_us": np.asarray(bases, dtype=np.int64),
            "off_us": generator.due_offsets_us(chunk, rate), "own_ts": False}


def _open_loop(chunk, n, rate):
    return _log(chunk, [generator.chunk_base_us(j, chunk, rate)
                        for j in range(n)], rate)


CASES = [
    ("open loop", 65536, 2_000, 500, _open_loop(64, 40, 100_000)),
    ("a small grid", 8, 2_000, 500, _open_loop(64, 40, 100_000)),
    ("more than a period", 65536, 2_000, 500, _open_loop(256, 20, 400_000)),
    ("a slide of two panes", 64, 3_000, 2_000, _open_loop(64, 40, 100_000)),
    ("two events a microsecond", 64, 200, 50, _open_loop(64, 40, 2_000_000)),
    # a silence of 1.5 ms: panes that hold nothing, inside windows that do
    ("empty panes", 64, 2_000, 500,
     _log(64, [0, 640, 1280, 3_500, 4_140], 100_000)),
    ("one chunk", 65536, 2_000, 500, _open_loop(64, 1, 100_000)),
]


@pytest.mark.parametrize("name,grid_side,win,slide,log", CASES,
                         ids=[c[0] for c in CASES])
@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11])
def test_expected_by_panes_equals_brute_force_over_whole_windows(
        name, grid_side, win, slide, log, seed):
    cfg = _small(grid_side, win, slide)
    fast, slow = oracle.expected(cfg, seed, log), oracle.brute_force(
        cfg, seed, log)
    assert len(slow["wid"]) > 0 and (slow["size"] > 0).all()
    for col in slow:
        assert np.array_equal(fast[col], slow[col]), col
    assert (np.diff(fast["wid"]) > 0).all()
    # ... and equals the whole-window reference of the sibling deployment
    # over the same stream, but for the time of the windows left open
    whole = spatial_wf_oracle.expected(cfg, seed, log)
    for col in ("wid", "size", "checksum", "_closes_at_us"):
        assert np.array_equal(fast[col], whole[col]), col
    closed = fast["_closes_at_us"] != oracle.NEVER
    assert np.array_equal(fast["ts"][closed], whole["ts"][closed])


def test_the_time_of_a_window_the_streams_end_leaves_open():
    """It carries its last pane's time: the end of the pane that holds the
    stream's last event."""
    log = _open_loop(64, 40, 100_000)     # events every 10 us to 25.59 ms
    want = oracle.expected(_small(), 5, log)
    last_pane_end = (25_590 // 500 + 1) * 500 - 1
    assert np.array_equal(
        want["ts"], np.minimum(want["wid"] * 500 + 1_999, last_pane_end))
    assert (want["ts"] == last_pane_end).sum() == 4     # 2,000 / 500


@pytest.mark.parametrize("rate", [100_000, 2_000_000, 3_000_000])
def test_bisection_finds_what_the_laid_out_times_hold(rate):
    log = _open_loop(64, 50, rate)
    times = oracle._Times(log)
    ts = spatial_wf_oracle._event_times(log)
    assert times.n == len(ts)
    probe = np.concatenate([np.arange(-3, int(ts[-1]) + 5, 7), ts[::97],
                            ts[::97] + 1])
    assert np.array_equal(times.first_at_or_after(probe),
                          np.searchsorted(ts, probe, side="left"))
    assert np.array_equal(times.at(np.arange(len(ts))), ts)


def test_event_times_that_fall_are_refused():
    with pytest.raises(ValueError, match="event times fall"):
        oracle.expected(_small(), 1, _log(64, [0, 600], 100_000))
    with pytest.raises(ValueError, match="generator's event times"):
        oracle.expected(_small(), 1, dict(_open_loop(64, 2, 100_000),
                                          own_ts=True))


def test_an_empty_log_has_no_result_and_a_gap_leaves_windows_out():
    none = oracle.expected(_small(), 3, _log(64, []))
    assert all(len(v) == 0 for v in none.values())
    gap = oracle.expected(_small(64), 3,
                          _log(64, [0, 640, 1280, 11_000, 11_640], 100_000))
    assert (np.diff(gap["wid"]) > 1).any()


def test_a_window_is_due_when_an_event_at_or_past_its_end_exists():
    log = _open_loop(64, 40, 100_000)
    want = oracle.expected(_small(), 5, log)
    ends = want["wid"] * 500 + 2_000
    due = want["_closes_at_us"] != oracle.NEVER
    assert np.array_equal(due, ends <= 25_590)
    assert np.array_equal(want["_closes_at_us"][due], ends[due])
    assert due.any() and not due.all()
    assert oracle.events_of_missing(_small(), 3) == 3


def test_the_stream_is_the_sibling_deployments():
    cfg = _small()
    for fn in ("columns", "id_shift", "period_events", "events_of_missing"):
        assert getattr(oracle, fn) is getattr(spatial_wf_oracle, fn)
    a = oracle.columns(cfg, 5, 0, 2048)
    assert a["x"].dtype == np.float32 and a["x"].max() < 65536
    assert oracle.pane_us(cfg) == 500
    assert oracle.pane_us(_small(64, 3_000, 2_000)) == 1_000


def test_the_largest_pane_frontier_of_each_window_is_noted():
    cfg, log = _small(8), _open_loop(64, 40, 100_000)
    want = oracle.expected(cfg, 3, log)
    ts = spatial_wf_oracle._event_times(log)
    px, py = oracle._period_points(cfg, 3, np.float32)
    for w, noted in zip(want["wid"].tolist(),
                        want["_pane_front_max"].tolist()):
        sizes = []
        for p in range(w, w + 4):
            held = np.flatnonzero((ts >= p * 500) & (ts < (p + 1) * 500))
            x, y = px[held % 2048], py[held % 2048]
            sizes.append(int(spatial_wf_oracle.skyline_mask_all_pairs(
                x, y).sum()))
        assert noted == max(sizes)


def test_float16_control_fails_the_comparison_and_float32_passes_it():
    cfg, log = _small(), _open_loop(64, 40, 100_000)
    for seed in (1, 2, 3):
        want = oracle.expected(cfg, seed, log)
        same = oracle.expected(cfg, seed, log, acc_dtype=np.float32)
        control = oracle.expected(cfg, seed, log, acc_dtype=np.float16)

        def numbers(table):
            return check.compare({k: v for k, v in table.items()
                                  if not k.startswith("_")}, want)[0]
        assert check.verdict(numbers(same))[0]
        wrong = numbers(control)
        assert wrong["wrong.checksum"] > len(want["wid"]) // 2
        assert not check.verdict(wrong)[0]
        assert wrong["missing"] == wrong["unexpected"] == 0
