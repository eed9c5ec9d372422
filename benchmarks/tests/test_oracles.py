"""Each plain reference against a brute-force per-window loop at a small
size: a stream longer than one template period, a partial last window, and
both kinds of event time (one stamp per chunk, a due time per event)."""

import importlib

import numpy as np
import pytest

from conftest import load
from harness import generator


def _small(cfg_name):
    cfg = load("configs", f"{cfg_name}.json")
    if cfg_name == "pipe_cb":
        cfg["stream"]["template_events"] = 64 * 24       # 24 rows a key
        cfg["shapes"].update(win=8, slide=2)
    else:
        cfg["stream"]["recurrence_period"] = 1000
        cfg["shapes"].update(n_campaigns=10, ads_per_campaign=3,
                             win_us=1000)
    return cfg


def _log(chunk, bases, rate=None):
    return {"chunk": chunk, "base_us": np.asarray(bases, dtype=np.int64),
            "off_us": generator.due_offsets_us(chunk, rate)}


CASES = [
    # (configuration, chunk, chunk bases in us, rate)
    ("pipe_cb", 64 * 5, [0, 7, 19, 40, 41, 90, 1000], None),      # > 1 period
    ("pipe_cb", 64 * 3, [3 * i for i in range(11)], None),
    ("pipe_cb", 64 * 4, [generator.chunk_base_us(j, 256, 50000)
                         for j in range(9)], 50000),
    ("ysb_kf", 700, [0, 300, 999, 1000, 1800, 2500, 4100], None),  # > 1 period
    ("ysb_kf", 250, [generator.chunk_base_us(j, 250, 200000)
                     for j in range(13)], 200000),                 # spans wins
]


@pytest.mark.parametrize("name,chunk,bases,rate", CASES)
@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11])
def test_expected_equals_brute_force(name, chunk, bases, rate, seed):
    oracle = importlib.import_module(f"configs.{name}_oracle")
    cfg = _small(name)
    log = _log(chunk, bases, rate)
    fast, slow = oracle.expected(cfg, seed, log), oracle.brute_force(
        cfg, seed, log)
    assert len(slow["key"]) > 0
    order = np.lexsort((fast["wid"], fast["key"]))
    for col in slow:
        assert np.array_equal(fast[col][order], slow[col]), col


@pytest.mark.parametrize("name,chunk,bases,rate", CASES[:1] + CASES[3:4])
def test_partial_last_window_is_there_and_never_due(name, chunk, bases, rate):
    oracle = importlib.import_module(f"configs.{name}_oracle")
    cfg = _small(name)
    want = oracle.expected(cfg, 5, _log(chunk, bases, rate))
    never = want["_closes_at_us"] == oracle.NEVER
    assert never.any() and not never.all()
    # the windows that only the end of the stream closes are each key's last
    for k in np.unique(want["key"]):
        wids = want["wid"][want["key"] == k]
        open_wids = want["wid"][(want["key"] == k) & never]
        assert open_wids.min() > wids[~np.isin(wids, open_wids)].max()


@pytest.mark.parametrize("name,narrow", [("pipe_cb", np.int16),
                                         ("ysb_kf", np.int16)])
def test_lower_precision_control_differs_at_cell_size(name, narrow):
    """The control: the reference with the accumulator one step narrower
    than the configuration states.  At the cell's own sizes it must differ
    from the exact reference, or the comparison could not catch a PR that
    narrows the accumulate."""
    from harness import check
    oracle = importlib.import_module(f"configs.{name}_oracle")
    cfg = load("configs", f"{name}.json")
    if name == "pipe_cb":
        cfg["stream"]["template_events"] = 1 << 16     # a test run's memory
        log = _log(1 << 14, [1000 * j for j in range(8)])
    else:
        # 60 chunks a second for 12 s: one full 10 s window at full speed
        log = _log(200000, [16667 * j for j in range(720)])
    exact = oracle.expected(cfg, 3, log)
    control = oracle.expected(cfg, 3, log, acc_dtype=narrow)
    numbers, _ = check.compare(
        {k: v for k, v in control.items() if not k.startswith("_")}, exact)
    ok, _lines = check.verdict(numbers)
    assert not ok
    wrong = [k for k, v in numbers.items() if k.startswith("wrong.") and v]
    assert wrong and numbers["missing"] == numbers["unexpected"] == 0
