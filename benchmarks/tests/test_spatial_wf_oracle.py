"""The ``spatial_wf`` reference: the sort-based skyline against the all-pairs
definition on draws with ties, duplicates, an empty window and one point; the
stream as a function of ``(seed, event index)``; which windows are due; the
float16 control; and the assertion that keeps a float32 checksum exact."""

import numpy as np
import pytest

from conftest import load
from configs import spatial_wf_oracle as oracle
from harness import check, generator


def _small(grid_side=65536, win_us=2_000, slide_us=500):
    cfg = load("configs", "spatial_wf.json")
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


POINTS = {
    "empty": ([], []),
    "one point": ([5], [9]),
    "identical points": ([2, 2, 2], [7, 7, 7]),
    "a tie in x": ([1, 1, 3], [4, 2, 1]),
    "a tie in y": ([1, 2, 3], [5, 5, 5]),
    "a duplicate of a dominated point": ([1, 4, 4], [1, 4, 4]),
    "a staircase": ([0, 1, 2, 3], [3, 2, 1, 0]),
    "a chain": ([0, 1, 2, 3], [0, 1, 2, 3]),
}


@pytest.mark.parametrize("name", sorted(POINTS))
def test_skyline_by_sort_equals_the_definition_on_hand_made_points(name):
    x, y = (np.asarray(v, dtype=np.float64) for v in POINTS[name])
    fast = oracle.skyline_mask(x, y)
    assert np.array_equal(fast, oracle.skyline_mask_all_pairs(x, y))
    assert fast.dtype == bool and len(fast) == len(x)


def test_hand_made_answers():
    def alive(name):
        x, y = (np.asarray(v, dtype=np.float64) for v in POINTS[name])
        return oracle.skyline_mask(x, y).tolist()
    assert alive("identical points") == [True, True, True]
    assert alive("a tie in x") == [False, True, True]
    assert alive("a tie in y") == [True, False, False]
    assert alive("a duplicate of a dominated point") == [True, False, False]
    assert alive("a staircase") == [True] * 4
    assert alive("a chain") == [True, False, False, False]


@pytest.mark.parametrize("grid_side", [2, 5, 64, 65536])
@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11])
def test_skyline_by_sort_equals_the_definition_on_draws(grid_side, seed):
    """A small grid makes ties and duplicates the rule."""
    rng = np.random.default_rng(seed + grid_side)
    for n in (1, 2, 17, 300):
        x = rng.integers(0, grid_side, n).astype(np.float64)
        y = rng.integers(0, grid_side, n).astype(np.float64)
        assert np.array_equal(oracle.skyline_mask(x, y),
                              oracle.skyline_mask_all_pairs(x, y))


CASES = [
    ("open loop", 65536, _open_loop(64, 40, 100_000)),
    ("open loop, a small grid", 8, _open_loop(64, 40, 100_000)),
    ("more than a period", 65536, _open_loop(256, 20, 400_000)),
    # a silence of 10 ms: the windows inside it hold nothing, give no result
    ("a gap", 64, _log(64, [0, 640, 1280, 11_000, 11_640], 100_000)),
    ("one chunk", 65536, _open_loop(64, 1, 100_000)),
]


@pytest.mark.parametrize("name,grid_side,log", CASES,
                         ids=[c[0] for c in CASES])
@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11])
def test_expected_equals_brute_force(name, grid_side, log, seed):
    cfg = _small(grid_side)
    fast, slow = oracle.expected(cfg, seed, log), oracle.brute_force(
        cfg, seed, log)
    assert len(slow["wid"]) > 0 and (slow["size"] > 0).all()
    for col in slow:
        assert np.array_equal(fast[col], slow[col]), col
    assert (np.diff(fast["wid"]) > 0).all()
    assert np.array_equal(fast["ts"], fast["wid"] * 500 + 2_000 - 1)


def test_a_gap_leaves_windows_out_and_an_empty_log_has_no_result():
    want = oracle.expected(_small(64), 3, CASES[3][2])
    assert (np.diff(want["wid"]) > 1).any()
    none = oracle.expected(_small(), 3, _log(64, []))
    assert all(len(v) == 0 for v in none.values())


def test_a_window_is_due_when_an_event_at_or_past_its_end_exists():
    log = _open_loop(64, 40, 100_000)           # events every 10 us to 25.59 ms
    want = oracle.expected(_small(), 5, log)
    ends = want["ts"] + 1
    last = int(log["base_us"][-1] + log["off_us"][-1])
    due = want["_closes_at_us"] != oracle.NEVER
    assert np.array_equal(due, ends <= last)
    assert np.array_equal(want["_closes_at_us"][due], ends[due])
    assert due.any() and not due.all()
    assert oracle.events_of_missing(_small(), 3) == 3


def test_the_stream_is_one_draw_rotated_by_the_seed_and_cycled():
    cfg = _small()
    a = oracle.columns(cfg, 0, 0, 2048)
    b = oracle.columns(cfg, 5, 0, 2048)
    assert a["x"].dtype == np.float32 and a["y"].dtype == np.float32
    assert np.array_equal(np.roll(a["x"], -5), b["x"])
    assert np.array_equal(np.roll(a["y"], -5), b["y"])
    assert np.array_equal(a["id"], np.arange(2048)) and not a["key"].any()
    assert oracle.id_shift(cfg, 4096) == 4096
    assert a["x"].min() >= 0 and a["x"].max() < 65536
    assert np.array_equal(a["x"], np.floor(a["x"]))         # on the grid
    big = oracle.columns(cfg, 2**31 + 11, 100, 64)
    assert np.array_equal(
        big["x"], oracle.columns(cfg, (2**31 + 11) % 2048, 100, 64)["x"])


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


def test_a_skyline_too_long_for_an_exact_float32_sum_is_refused():
    """129 points of up to 131,070 each can pass 2^24."""
    cfg = _small(grid_side=65536, win_us=100_000, slide_us=100_000)
    cfg["stream"]["template_events"] = 256
    pts = np.stack([np.arange(200, dtype=np.float32),
                    np.arange(200, dtype=np.float32)[::-1]], axis=1)
    oracle._POINTS.clear()
    key = (256, 65536, int(cfg["stream"]["draw_seed"]), 0)
    oracle._POINTS[key] = np.concatenate([pts, pts[:56]])
    try:
        with pytest.raises(AssertionError, match="2\\^24"):
            oracle.expected(cfg, 0, _open_loop(64, 4, 100_000))
    finally:
        oracle._POINTS.clear()
