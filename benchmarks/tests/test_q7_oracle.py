"""The plain reference of ``q7_highest_bid`` against its brute-force loop at a
small size (a stream longer than one template period, a partial last window,
one stamp per chunk and a due time per event), the stream's shape, and the
lower-precision control at the cell's own sizes."""

import numpy as np
import pytest

from conftest import load
from configs import q7_highest_bid_oracle as oracle
from harness import check, generator


def _small():
    cfg = load("configs", "q7_highest_bid.json")
    cfg["stream"]["template_events"] = 2000
    cfg["shapes"]["win_us"] = cfg["shapes"]["slide_us"] = 1000
    return cfg


def _log(chunk, bases, rate=None):
    return {"chunk": chunk, "base_us": np.asarray(bases, dtype=np.int64),
            "off_us": generator.due_offsets_us(chunk, rate)}


CASES = [
    (500, [0, 300, 999, 1000, 1800, 2500, 4100, 4100, 5200], None),
    (250, [generator.chunk_base_us(j, 250, 200000) for j in range(19)],
     200000),                                          # chunks span windows
]


@pytest.mark.parametrize("chunk,bases,rate", CASES)
@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11])
def test_expected_equals_brute_force(chunk, bases, rate, seed):
    cfg, log = _small(), _log(chunk, bases, rate)
    fast, slow = oracle.expected(cfg, seed, log), oracle.brute_force(
        cfg, seed, log)
    assert len(slow["wid"]) > 2
    for col in slow:
        assert np.array_equal(fast[col], slow[col]), col
    never = fast["_closes_at_us"] == oracle.NEVER
    assert never[-1] and not never[:-1].any()   # only the last window is open


def test_ties_go_to_the_lowest_id():
    cfg = _small()
    cols = oracle._period(cfg, 5)
    bids = np.flatnonzero(cols["event_type"] == oracle.BID)
    top = int(cols["price"].max()) + 1
    for i in (bids[400], bids[40], bids[900]):        # same window, out of order
        cols["price"][i] = top
    try:
        want = oracle.expected(cfg, 5, _log(2000, [0]))
        assert int(want["price"][0]) == top
        assert int(want["auction"][0]) == int(cols["auction"][bids[40]])
    finally:
        oracle._COLS.clear()


def test_the_stream_has_nexmarks_shape():
    cfg = load("configs", "q7_highest_bid.json")
    cfg["stream"]["template_events"] = 1 << 16
    c = oracle.columns(cfg, 3, 0, 1 << 16)
    et = c["event_type"]
    assert np.array_equal(np.bincount(et[:50]), [1, 3, 46])
    bid = et == oracle.BID
    price = c["price"][bid]
    assert price.min() >= 100 and price.max() <= 100_000_000
    # uniform in the exponent: a sixth of the bids per decade
    assert abs(np.mean(price < 1000) - 1 / 6) < 0.01
    assert not c["price"][~bid].any() and not c["auction"][~bid].any()
    # about half the bids go to the hot auction of their batch of 100
    hot = (c["auction"][bid] - 1000) % 100 == 0
    assert 0.45 < hot.mean() < 0.56
    assert c["bidder"][bid].min() >= 1000
    again = oracle.columns(cfg, 3, 1000, 500)
    assert np.array_equal(again["price"], c["price"][1000:1500])
    oracle._COLS.clear()
    other = oracle.columns(cfg, 4, 0, 1 << 16)
    assert not np.array_equal(other["price"], c["price"])


def test_lower_precision_control_differs_at_cell_size():
    """Prices compared in int16, one width under the device's int32: most
    exceed 32767 and wrap, so another bid wins every window."""
    cfg = load("configs", "q7_highest_bid.json")
    cfg["stream"]["template_events"] = 1 << 18      # a test run's memory
    # 40 chunks a second for 12 s: one full 10 s window at full speed
    log = _log(1 << 18, [25000 * j for j in range(480)])
    exact = oracle.expected(cfg, 3, log)
    control = oracle.expected(cfg, 3, log, acc_dtype=np.int16)
    numbers, _ = check.compare(
        {k: v for k, v in control.items() if not k.startswith("_")}, exact)
    ok, _lines = check.verdict(numbers)
    assert not ok and numbers["wrong.price"] == len(exact["wid"])
    assert numbers["missing"] == numbers["unexpected"] == 0
    assert numbers["wrong.count"] == numbers["wrong.lastUpdate"] == 0
