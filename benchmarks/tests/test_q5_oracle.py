"""The plain reference of ``q5_hot_items`` against its brute-force loop at a
small size (streams longer than one template period, windows without a bid, a
partial last window), auction ids running on across a cycle boundary, the two
new readers, and the lower-precision control at the cell's own sizes."""

import numpy as np
import pytest

from conftest import load
from configs import q5_hot_items_oracle as oracle
from harness import check, generator
from layer_metrics.readers import node_counter, stage_span


def _small(period=2000):
    cfg = load("configs", "q5_hot_items.json")
    cfg["stream"]["template_events"] = period
    cfg["shapes"]["win_us"], cfg["shapes"]["slide_us"] = 1000, 500
    return cfg


def _log(chunk, bases):
    return {"chunk": chunk, "base_us": np.asarray(bases, dtype=np.int64),
            "off_us": generator.due_offsets_us(chunk, None), "own_ts": False}


LOGS = [
    (500, [0, 300, 499, 500, 1800, 2500, 4100, 4100, 5200]),   # empty windows
    (250, [120 * j for j in range(40)]),              # five template periods
    (1000, [0, 1, 2, 3, 2600]),
]


@pytest.mark.parametrize("chunk,bases", LOGS)
@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11])
def test_expected_equals_brute_force(chunk, bases, seed):
    cfg, log = _small(), _log(chunk, bases)
    fast, slow = oracle.expected(cfg, seed, log), oracle.brute_force(
        cfg, seed, log)
    assert len(slow["wid"]) > 2
    for col in slow:
        assert np.array_equal(fast[col], slow[col]), col
    # a window closes with the first chunk at or past its end
    ends = fast["wid"] * 500 + 1000
    base = np.asarray(bases)
    for end, closes in zip(ends.tolist(), fast["_closes_at_us"].tolist()):
        later = base[base >= end]
        assert closes == (int(later[0]) if len(later) else oracle.NEVER)
    oracle._nexmark._COLS.clear()


def test_a_window_without_a_bid_gives_no_result():
    want = oracle.expected(_small(), 3, _log(500, [0, 100, 4100]))
    assert want["wid"].tolist() == [0, 7, 8]
    oracle._nexmark._COLS.clear()


def test_ties_go_to_the_lowest_auction_id():
    """Every cycle repeats the template's counts on auction ids that run
    on: a window's maximum is tied across the cycles it holds, and the
    lowest auction id wins."""
    cfg = _small(period=10000)
    log = _log(10000, [0, 10, 20, 30])
    want = oracle.expected(cfg, 2, log)
    cols = oracle._nexmark._period(cfg, 2)
    bid = cols["event_type"] == oracle.BID
    counts = {}
    for cycle in range(4):
        for a in (cols["auction"][bid] + cycle * 600).tolist():
            counts[a] = counts.get(a, 0) + 1
    top = max(counts.values())
    tied = sorted(a for a, n in counts.items() if n == top)
    assert len(tied) >= 2
    assert (int(want["auction"][0]), int(want["num"][0])) == (tied[0], top)
    assert int(want["bids"][0]) == 4 * int(bid.sum())
    oracle._nexmark._COLS.clear()


@pytest.mark.parametrize("seed", [1, 2**31 + 5])
def test_run_on_is_exact_across_a_cycle_boundary(seed):
    """The harness's generator runs ids on; ``run_on`` runs the bids'
    auctions and keys on by the auctions a period opens, and nothing else."""
    cfg = _small(period=5000)
    per_cycle = oracle.auctions_per_period(cfg)
    assert per_cycle == 300
    cell = load("configs", "q5_hot_items.json")
    assert oracle.auctions_per_period(cell) == 480_000
    dtype = np.dtype([("key", "i8"), ("id", "i8"), ("ts", "i8"),
                      ("marker", "?"), ("event_type", "i1"),
                      ("auction", "i8"), ("bidder", "i8"), ("price", "i8")])
    templates, shift, own_ts = generator.build_templates(
        oracle, cfg, seed, dtype, 1000)
    assert len(templates) == 5 and shift == 5000 and not own_ts
    for j in (4, 5, 9, 10, 14):                 # cycles 0, 1, 1, 2, 2
        cycle = j // 5
        chunk = templates[j % 5].copy()
        chunk["id"] += cycle * shift            # as generator._make does
        before = chunk.copy()
        oracle.run_on(cfg, chunk)
        bid = before["event_type"] == oracle.BID
        assert bid.sum() == 920
        for col in ("key", "auction"):
            assert np.array_equal(chunk[col][bid],
                                  before[col][bid] + cycle * per_cycle)
            assert not chunk[col][~bid].any()
        for col in ("id", "ts", "event_type", "bidder", "price"):
            assert np.array_equal(chunk[col], before[col])
    # an auction of cycle 0 is never bid on in cycle 1
    first = templates[4].copy()
    second = oracle.run_on(cfg, _advance(templates[0].copy(), shift))
    assert first["auction"].max() < second["auction"][
        second["event_type"] == oracle.BID].min() + 120
    assert not np.intersect1d(
        templates[0]["auction"][templates[0]["event_type"] == oracle.BID],
        second["auction"][second["event_type"] == oracle.BID]).size
    oracle._nexmark._COLS.clear()


def _advance(chunk, shift):
    chunk["id"] += shift
    return chunk


def test_a_chunk_with_more_than_one_event_time_is_refused():
    log = _log(500, [0, 700])
    log["off_us"] = generator.due_offsets_us(500, 100000)
    with pytest.raises(ValueError, match="one event time"):
        oracle.expected(_small(), 1, log)


def _cell_log():
    """Two 10 s windows of the cell's chunks at about 10M events/s."""
    cfg = load("configs", "q5_hot_items.json")
    cfg["stream"]["template_events"] = 1_000_000      # a test run's memory
    return cfg, _log(250_000, [25000 * j for j in range(480)])


@pytest.mark.parametrize("width,right", [(np.int8, False), (np.int16, True)])
def test_lower_precision_control_at_cell_size(width, right):
    """A hot auction takes about 800 bids: int8 wraps it, so every window's
    count reads wrong (the auction itself only where the hot counts straddle
    a multiple of 256); int16, the nearest width under the device's int32,
    holds every count and reads correct."""
    cfg, log = _cell_log()
    exact = oracle.expected(cfg, 3, log)
    assert len(exact["wid"]) == 3 and 700 < exact["num"].min() < 1200
    control = oracle.expected(cfg, 3, log, acc_dtype=width)
    numbers, _ = check.compare(
        {k: v for k, v in control.items() if not k.startswith("_")}, exact)
    ok, _lines = check.verdict(numbers)
    assert ok == right
    if not right:
        assert numbers["wrong.num"] == 3 and (control["num"] < 128).all()
        assert numbers["wrong.bids"] == numbers["wrong.lastUpdate"] == 0
        assert numbers["missing"] == numbers["unexpected"] == 0
    oracle._nexmark._COLS.clear()


# -- the readers this configuration brought -----------------------------------

NODES = [{"node": "g_01_count.0", "rcv_tuples": 625, "keys_live_peak": 40},
         {"node": "g_02_count.1", "rcv_tuples": 125, "keys_live_peak": 30},
         {"node": "g_03_count.2", "rcv_tuples": 250, "keys_live_peak": 30},
         {"node": "g_04_rekey.0", "rcv_tuples": 9000}]


@pytest.mark.parametrize("params,value", [
    ({"counter": "keys_live_peak", "how": "sum"}, 100.0),
    ({"counter": "keys_live_peak", "how": "max"}, 40.0),
    ({"counter": "rcv_tuples", "among": "keys_live_peak",
      "how": "max_share"}, 62.5),
])
def test_node_counter(params, value):
    got = node_counter.read({"nodes": NODES}, params)
    assert got["value"] == value and "count.0" in got["note"]


@pytest.mark.parametrize("nodes", [[], NODES[3:]])
def test_node_counter_finds_nothing_where_no_node_reports_it(nodes):
    """As on a parent whose cores hold no such counter."""
    assert node_counter.read({"nodes": nodes}, {
        "counter": "keys_live_peak", "how": "sum"}) is None


def test_stage_span_divides_by_the_stages_own_workers():
    obs = {"profile_spans": {"stream_fire": (3.0, 12)}, "window_s": 50.0,
           "cfg": {"shapes": {"count_degree": 4}}}
    params = {"span": "stream_fire", "workers": "count_degree"}
    assert stage_span.read(obs, params)["value"] == 1.5
    assert stage_span.read(dict(obs, profile_spans={}), params) is None
