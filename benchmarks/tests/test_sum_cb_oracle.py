"""The ``sum_cb`` reference against a brute-force per-window loop at a small
size, and its lower-precision controls at the cell's own sizes."""

import numpy as np
import pytest

from conftest import load
from configs import sum_cb_oracle as oracle
from harness import check, generator


def _small():
    cfg = load("configs", "sum_cb.json")
    cfg["stream"]["template_events"] = 64 * 24           # 24 rows a key
    cfg["shapes"].update(win=8, slide=2)
    return cfg


def _log(chunk, bases, rate=None):
    return {"chunk": chunk, "base_us": np.asarray(bases, dtype=np.int64),
            "off_us": generator.due_offsets_us(chunk, rate), "own_ts": False}


CASES = [
    (64 * 5, [0, 7, 19, 40, 41, 90, 1000], None),         # > 1 period
    (64 * 3, [3 * i for i in range(11)], None),
    (64 * 4, [generator.chunk_base_us(j, 256, 50000) for j in range(9)],
     50000),                                              # a due time each
    (64, [5], None),                                      # one row a key
]


@pytest.mark.parametrize("chunk,bases,rate", CASES)
@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11])
def test_expected_equals_brute_force(chunk, bases, rate, seed):
    cfg = _small()
    log = _log(chunk, bases, rate)
    fast, slow = oracle.expected(cfg, seed, log), oracle.brute_force(
        cfg, seed, log)
    assert len(slow["key"]) > 0
    order = np.lexsort((fast["wid"], fast["key"]))
    for col in slow:
        assert np.array_equal(fast[col][order], slow[col]), col


def test_partial_last_windows_are_there_and_never_due():
    want = oracle.expected(_small(), 5, _log(*CASES[0][:2]))
    never = want["_closes_at_us"] == oracle.NEVER
    assert never.any() and not never.all()
    for k in np.unique(want["key"]):
        mine = want["key"] == k
        assert want["wid"][mine & never].min() \
            > want["wid"][mine & ~never].max()


def test_an_empty_log_has_no_result():
    want = oracle.expected(_small(), 5, _log(64, []))
    assert all(len(v) == 0 for v in want.values())


def _at_cell_size(narrow):
    cfg = load("configs", "sum_cb.json")
    cfg["stream"]["template_events"] = 1 << 16            # a test run's memory
    log = _log(1 << 14, [1000 * j for j in range(8)])
    exact = oracle.expected(cfg, 3, log)
    control = oracle.expected(cfg, 3, log, acc_dtype=narrow)
    numbers, _ = check.compare(
        {k: v for k, v in control.items() if not k.startswith("_")}, exact)
    return numbers, len(exact["key"])


def test_int8_control_differs_at_cell_size():
    """The control the configuration names: the first accumulator width that
    cannot hold a window's sum.  It must read wrong, or the comparison could
    not catch a PR that narrows the accumulate too far."""
    cfg = load("configs", "sum_cb.json")
    assert cfg["precision"]["control"].split()[0] == "int8"
    numbers, n = _at_cell_size(np.int8)
    assert not check.verdict(numbers)[0]
    assert numbers["wrong.value"] > 0.9 * n
    assert numbers["missing"] == numbers["unexpected"] == 0


def test_int16_holds_every_window_of_this_deployment():
    """The nearest width under the device's int32: 256 x 99 = 25,344 fits, so
    an int16 accumulate is no fault here and reads correct -- which is why
    the control is int8."""
    cfg = load("configs", "sum_cb.json")
    shp = cfg["shapes"]
    assert shp["win"] * (shp["value_range"][1] - 1) < 2 ** 15
    numbers, _ = _at_cell_size(np.int16)
    assert check.verdict(numbers)[0]
