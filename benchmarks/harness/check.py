"""The comparison that decides ``correct``: every window result of the run
against the plain reference, as exact integers.

Both sides are tables (dicts of equal-length int64 columns) that share the
columns ``key`` and ``wid``; every other column is a value compared exactly
(but for the reference's own notes, whose names start with ``_``).
The numbers compared, each with the limit 0:

``missing``        results the reference has and the run lacks
``unexpected``     results the run has and the reference lacks
``duplicates``     (key, wid) pairs the run delivered more than once
``wrong.<column>`` results whose ``<column>`` differs from the reference
``out_of_order``   results that reached the sink after a later window of the
                   same key (the run's table is in arrival order)

numpy only.
"""

from __future__ import annotations

import numpy as np


def _pair(table, wid_span):
    return table["key"].astype(np.int64) * wid_span + table["wid"]


def _in_order(x):
    return len(x) < 2 or bool((x[1:] >= x[:-1]).all())


def _by_key(key):
    """The stable order that groups a table by key, arrival order kept inside
    each key.  A few keys sort as 16-bit integers, which numpy does by radix
    in a fraction of the time."""
    if len(key) and 0 <= key.min() and key.max() < 1 << 15:
        key = key.astype(np.int16)
    return np.argsort(key, kind="stable")


def compare(got, want):
    """``(numbers, (rows_got, rows_want, missing))``: the numbers above; for
    each matched pair the row of the run's table and of the reference's; and
    for each reference row whether the run lacks it.  Reference columns whose
    name starts with ``_`` are the reference's own notes and are not
    compared.

    A sound run delivers each key's windows in order and a reference lists
    its results by (key, wid), so both tables are as a rule in order once the
    run's is grouped by key: the sorts are made only where they are not (some
    tens of millions of rows a run; the comparison is paid by every run)."""
    wid_span = int(max(got["wid"].max(initial=0),
                       want["wid"].max(initial=0))) + 1
    g, w = _pair(got, wid_span), _pair(want, wid_span)
    numbers = {}
    # arrival order per key: a stable sort by key keeps it
    by_key = _by_key(got["key"])
    k_sorted, wid_sorted = got["key"][by_key], got["wid"][by_key]
    same_key = k_sorted[1:] == k_sorted[:-1]
    numbers["out_of_order"] = int(np.count_nonzero(
        same_key & (wid_sorted[1:] < wid_sorted[:-1])))
    # the run's pairs in order, ties in arrival order (as a stable sort of
    # ``g`` gives them): grouped by key they are in order already unless a
    # result came out of order
    g_order, g_sorted = by_key, g[by_key]
    if not _in_order(g_sorted):
        again = np.argsort(g_sorted, kind="stable")
        g_order, g_sorted = g_order[again], g_sorted[again]
    numbers["duplicates"] = int(np.count_nonzero(g_sorted[1:] == g_sorted[:-1]))
    first = np.ones(len(g_sorted), dtype=bool)
    first[1:] = g_sorted[1:] != g_sorted[:-1]
    g_uniq, g_rows = g_sorted[first], g_order[first]
    w_order = None if _in_order(w) else np.argsort(w, kind="stable")
    w_sorted = w if w_order is None else w[w_order]
    pos = np.searchsorted(g_uniq, w_sorted)
    pos_c = np.minimum(pos, max(len(g_uniq) - 1, 0))
    found = (g_uniq[pos_c] == w_sorted) if len(g_uniq) else \
        np.zeros(len(w_sorted), dtype=bool)
    numbers["missing"] = int(np.count_nonzero(~found))
    numbers["unexpected"] = int(len(g_uniq) - np.count_nonzero(found))
    rows_g = g_rows[pos_c[found]]
    rows_w = np.flatnonzero(found) if w_order is None else w_order[found]
    for col in want:
        if col in ("key", "wid") or col.startswith("_"):
            continue
        numbers[f"wrong.{col}"] = int(np.count_nonzero(
            got[col][rows_g] != want[col][rows_w]))
    missing = ~found if w_order is None else ~found[np.argsort(w_order)]
    return numbers, (rows_g, rows_w, missing)


def beside_limits(numbers, limits=None):
    """``{name: {"value", "limit"}}``: each number compared beside its limit
    (0 unless ``limits`` says otherwise)."""
    limits = limits or {}
    return {name: {"value": value, "limit": limits.get(name, 0)}
            for name, value in numbers.items()}


def verdict(numbers, limits=None):
    """Lines ``name value limit`` for the run's output, and whether every
    number is within its limit."""
    table = beside_limits(numbers, limits)
    return (all(v["value"] <= v["limit"] for v in table.values()),
            [f"check {name} = {v['value']} (limit {v['limit']})"
             for name, v in table.items()])
