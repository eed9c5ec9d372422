"""The ``sum_tb_late`` reference: its answers against a loop over events at a
small size, the disorder its stream states, which results it calls due, and
its lower-precision control at the cell's own sizes."""

import numpy as np
import pytest

from conftest import load
from configs import sum_tb_late_oracle as oracle
from harness import check, generator


def _small(period=64 * 48):
    cfg = load("configs", "sum_tb_late.json")
    cfg["stream"]["template_events"] = period
    return cfg


def _log(chunk, bases):
    return {"chunk": chunk, "base_us": np.asarray(bases, dtype=np.int64),
            "off_us": generator.due_offsets_us(chunk, None), "own_ts": True}


#: (chunk, the chunks' bases): a loop slower than a chunk a span, one faster
#: (two chunks inside one span), one that stalls, more than one period
CASES = [
    (64 * 8, [13000 * j for j in range(20)]),
    (64 * 8, [4000 * j for j in range(30)]),
    (64 * 16, [0, 9000, 21000, 2500000, 2512000, 5000000, 9000000]),
    (64 * 4, [250000 * j for j in range(40)]),
    (64, [5]),
]


@pytest.mark.parametrize("chunk,bases", CASES)
@pytest.mark.parametrize("seed", [0, 7, 2**31 + 11])
def test_expected_equals_brute_force(chunk, bases, seed):
    cfg = _small()
    log = _log(chunk, bases)
    fast, slow = oracle.expected(cfg, seed, log), oracle.brute_force(
        cfg, seed, log)
    assert len(slow["key"]) > 0
    assert (np.diff(fast["key"] * 10**6 + fast["wid"]) > 0).all()
    for col in slow:
        assert np.array_equal(fast[col], slow[col]), col
    assert fast["wid"].min() >= 0       # counted from the first that can be
    assert oracle.wid_offset(cfg) == 15


def test_windows_before_time_zero_hold_the_events_delayed_past_it():
    cfg = _small()
    want = oracle.expected(cfg, 3, _log(64 * 8, [13000 * j for j in range(9)]))
    index = want["wid"] - oracle.wid_offset(cfg)
    assert index.min() < -4 and index.max() == 0
    assert (want["ts"] == index * 250000 + 999999).all()
    cols = oracle.columns(cfg, 3, 0, 64 * 8 * 6)
    assert want["value"][index < -3].sum() > 0
    # every event lies in four windows
    log = _log(64 * 8, [13000 * j for j in range(6)])
    assert oracle.expected(cfg, 3, log)["value"].sum() == \
        4 * cols["value"].sum()


@pytest.mark.parametrize("seed", [1, 2**31 + 3])
def test_the_stream_is_as_disordered_as_it_says(seed):
    cfg = load("configs", "sum_tb_late.json")
    cfg["stream"]["template_events"] = 1 << 18
    st = cfg["stream"]
    chunk = 1 << 14
    cols = oracle.columns(cfg, seed, chunk * 3, chunk)
    ts = cols["ts"]
    delayed = ts < 0
    assert abs(delayed.mean() - 1 / st["delayed_one_in"]) < 0.01
    assert ts[delayed].min() >= -st["delay_max_us"] and ts[delayed].max() <= -1
    assert np.ptp(-ts[delayed]) > 0.9 * st["delay_max_us"]   # uniform, wide
    on = ts[~delayed]
    assert on.min() >= 0 and on.max() < st["chunk_span_us"]
    assert (np.diff(on) >= 0).all() and on.max() > 0.99 * st["chunk_span_us"]
    # never a run of neighbours: the longest run of delayed events is short
    runs = np.diff(np.flatnonzero(np.diff(np.concatenate(
        ([0], delayed.astype(np.int8), [0])))))[::2]
    assert runs.max() <= 6
    assert (cols["key"] == np.arange(chunk * 3, chunk * 4) % 64).all()
    assert cols["value"].min() >= 0 and cols["value"].max() < 100
    # the hold-back covers it, also where the loop creates a chunk every
    # 3 ms, three inside one span
    for step in (3000, 13000):
        d = oracle.disorder(cfg, seed, _log(chunk, [step * j
                                                    for j in range(12)]))
        assert d["farthest_behind_us"] <= cfg["shapes"]["holdback_us"]
        assert d["farthest_behind_us"] > 0.99 * st["delay_max_us"]
        assert abs(d["delayed_share"] - 0.1) < 0.01
        if step > st["chunk_span_us"]:
            # chunks apart: behind their key's newest are the delayed ones
            assert 0.09 < d["behind_key_share"] < 0.11
        else:
            # chunks inside one another's span: most of a chunk's events
            # lie behind the one before's last
            assert d["behind_key_share"] > 0.5


def test_every_seed_carries_the_same_stream_in_another_phase():
    cfg = _small(64 * 1024)
    a = oracle.columns(cfg, 0, 0, 64 * 1024)
    b = oracle.columns(cfg, 5, 0, 64 * 1024)
    assert not np.array_equal(a["value"], b["value"])
    assert np.array_equal(np.roll(a["value"], -5 * 64), b["value"])
    assert np.array_equal(np.sort(a["ts"][a["ts"] < 0]),
                          np.sort(b["ts"][b["ts"] < 0]))


def test_a_result_is_due_once_an_event_passes_its_end_by_the_hold_back():
    cfg = _small()
    hold = cfg["shapes"]["holdback_us"]
    bases = [400000 * j for j in range(16)]
    log = _log(64 * 8, bases)
    want = oracle.expected(cfg, 9, log)
    # every event's time, in arrival order
    times = np.concatenate([
        base + oracle.columns(cfg, 9, (j * 64 * 8) % (64 * 48), 64 * 8)["ts"]
        for j, base in enumerate(bases)])
    for end, closes in zip((want["ts"] + 1).tolist(),
                           want["_closes_at_us"].tolist()):
        past = np.flatnonzero(times >= end + hold)
        assert closes == (int(times[past[0]]) if len(past) else oracle.NEVER)
    never = want["_closes_at_us"] == oracle.NEVER
    assert never.any() and not never.all()
    assert want["ts"][never].min() > want["ts"][~never].max()


def test_an_empty_log_has_no_result():
    want = oracle.expected(_small(), 5, _log(64, []))
    assert all(len(v) == 0 for v in want.values())


def test_a_log_without_its_own_times_is_refused():
    log = dict(_log(64, [5]), own_ts=False)
    with pytest.raises(ValueError, match="own event times"):
        oracle.expected(_small(), 5, log)


def _control(narrow):
    cfg = load("configs", "sum_tb_late.json")
    cfg["stream"]["template_events"] = 1 << 18            # a test run's memory
    log = _log(1 << 16, [13000 * j for j in range(16)])   # 5M events a second
    exact = oracle.expected(cfg, 3, log)
    control = oracle.expected(cfg, 3, log, acc_dtype=narrow)
    numbers, _ = check.compare(
        {k: v for k, v in control.items() if not k.startswith("_")}, exact)
    return numbers, exact


def test_int16_control_differs_at_cell_size():
    """The control the configuration names: one accumulator width under the
    device's int32.  It must read wrong, or the comparison could not catch a
    PR that narrows the accumulate too far."""
    cfg = load("configs", "sum_tb_late.json")
    assert cfg["precision"]["control"].split()[0] == "int16"
    numbers, exact = _control(np.int16)
    assert not check.verdict(numbers)[0]
    full = exact["value"] > 32767
    assert numbers["wrong.value"] == full.sum() > 0.2 * len(full)
    assert numbers["missing"] == numbers["unexpected"] == 0


def test_the_devices_own_width_reads_correct():
    numbers, _ = _control(np.int32)
    assert check.verdict(numbers)[0]


def test_the_comparison_takes_the_references_window_numbering():
    """``check.compare`` pairs (key, wid) as one number and needs no window
    below 0: the reference counts from the first that can hold an event."""
    cfg = _small()
    want = oracle.expected(cfg, 1, _log(64 * 8, [13000 * j
                                                 for j in range(20)]))
    got = {k: v.copy() for k, v in want.items() if not k.startswith("_")}
    numbers, _ = check.compare(got, want)
    assert check.verdict(numbers)[0]
    got["value"][5] += 1
    numbers, _ = check.compare(got, want)
    assert numbers["wrong.value"] == 1 and numbers["duplicates"] == 0
