"""The comparison that decides ``correct`` against a plain dictionary loop,
on tables made at random: sound ones, and ones with every kind of fault the
numbers name -- so that the sorts it skips for tables already in order are
skipped only where that changes nothing."""

import numpy as np
import pytest

from harness import check


def _tables(rng, n_keys, n_wids, big_keys=False):
    """A reference by (key, wid), and a sound run: each key's windows in
    order, the keys interleaved as they arrive."""
    keys = np.arange(n_keys, dtype=np.int64) * (70_000 if big_keys else 1)
    want = {"key": np.repeat(keys, n_wids),
            "wid": np.tile(np.arange(n_wids, dtype=np.int64), n_keys)}
    want["value"] = rng.integers(0, 1000, size=len(want["key"]))
    want["_note"] = np.arange(len(want["key"]), dtype=np.int64)
    # arrival: a random merge of the keys' sequences
    arrival = np.argsort(rng.random(len(want["key"])) + want["wid"],
                         kind="stable")
    got = {c: want[c][arrival].copy() for c in ("key", "wid", "value")}
    return got, want


def _slow(got, want):
    """The same numbers and pairings by loops over rows."""
    numbers = {"out_of_order": 0, "duplicates": 0}
    last, first_row = {}, {}
    for row, (k, wid) in enumerate(zip(got["key"].tolist(),
                                       got["wid"].tolist())):
        if k in last and wid < last[k]:
            numbers["out_of_order"] += 1
        last[k] = wid
        if (k, wid) in first_row:
            numbers["duplicates"] += 1
        else:
            first_row[(k, wid)] = row
    pairs = list(zip(want["key"].tolist(), want["wid"].tolist()))
    missing = np.asarray([p not in first_row for p in pairs], dtype=bool)
    numbers["missing"] = int(missing.sum())
    numbers["unexpected"] = len(set(first_row) - set(pairs))
    matched = [(first_row[p], i) for i, p in enumerate(pairs)
               if p in first_row]
    numbers["wrong.value"] = sum(
        int(got["value"][r] != want["value"][i]) for r, i in matched)
    return numbers, sorted(matched), missing


def _alter(what, rng, got, want):
    n = len(got["key"])
    if what == "sound":
        return got, want
    if what == "a result out of order":
        rows = np.flatnonzero(got["key"] == got["key"][0])
        order = np.arange(n)                # two results of one key swapped
        order[rows[3]], order[rows[2]] = rows[2], rows[3]
        return {c: v[order] for c, v in got.items()}, want
    if what == "results missing and one twice":
        keep = np.r_[0:5, 7:n, 9]
        return {c: v[keep] for c, v in got.items()}, want
    if what == "results nobody expected":
        extra = {"key": np.asarray([got["key"].max() + 3, got["key"][0]]),
                 "wid": np.asarray([0, got["wid"].max() + 5]),
                 "value": np.asarray([1, 2])}
        return {c: np.concatenate([v, extra[c]]) for c, v in got.items()}, want
    if what == "values altered":
        got["value"][[1, n // 2, n - 1]] += 1
        return got, want
    if what == "a reference out of order":
        order = rng.permutation(len(want["key"]))
        return got, {c: v[order] for c, v in want.items()}
    raise AssertionError(what)


WHAT = ["sound", "a result out of order", "results missing and one twice",
        "results nobody expected", "values altered",
        "a reference out of order"]


@pytest.mark.parametrize("big_keys", [False, True])
@pytest.mark.parametrize("what", WHAT)
def test_compare_equals_a_loop_over_rows(what, big_keys):
    rng = np.random.default_rng(WHAT.index(what) * 2 + big_keys)
    got, want = _alter(what, rng, *_tables(rng, 5, 40, big_keys))
    numbers, (rows_g, rows_w, missing) = check.compare(got, want)
    slow, matched, slow_missing = _slow(got, want)
    assert numbers == slow
    assert sorted(zip(rows_g.tolist(), rows_w.tolist())) == matched
    assert np.array_equal(missing, slow_missing)
    assert check.verdict(numbers)[0] is (what in ("sound",
                                                  "a reference out of order"))


def test_empty_tables():
    empty = {c: np.zeros(0, dtype=np.int64) for c in ("key", "wid", "value")}
    numbers, (rows_g, rows_w, missing) = check.compare(empty, dict(empty))
    assert check.verdict(numbers)[0] and not len(rows_g) and not len(missing)
    got, want = _tables(np.random.default_rng(1), 2, 3)
    numbers, (_, _, missing) = check.compare(empty, want)
    assert numbers["missing"] == 6 and missing.all()
    numbers, _ = check.compare(got, {c: empty[c] for c in want
                                     if not c.startswith("_")})
    assert numbers["unexpected"] == 6
