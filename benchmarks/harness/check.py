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


def compare(got, want):
    """``(numbers, (rows_got, rows_want, missing))``: the numbers above; for
    each matched pair the row of the run's table and of the reference's; and
    for each reference row whether the run lacks it.  Reference columns whose
    name starts with ``_`` are the reference's own notes and are not
    compared."""
    wid_span = int(max(got["wid"].max(initial=0),
                       want["wid"].max(initial=0))) + 1
    g, w = _pair(got, wid_span), _pair(want, wid_span)
    numbers = {}
    # arrival order per key: a stable sort by key keeps it
    by_key = np.argsort(got["key"], kind="stable")
    k_sorted, wid_sorted = got["key"][by_key], got["wid"][by_key]
    same_key = k_sorted[1:] == k_sorted[:-1]
    numbers["out_of_order"] = int(np.count_nonzero(
        same_key & (wid_sorted[1:] < wid_sorted[:-1])))
    g_order = np.argsort(g, kind="stable")
    g_sorted = g[g_order]
    numbers["duplicates"] = int(np.count_nonzero(g_sorted[1:] == g_sorted[:-1]))
    first = np.ones(len(g_sorted), dtype=bool)
    first[1:] = g_sorted[1:] != g_sorted[:-1]
    g_uniq, g_rows = g_sorted[first], g_order[first]
    w_order = np.argsort(w, kind="stable")
    w_sorted = w[w_order]
    pos = np.searchsorted(g_uniq, w_sorted)
    pos_c = np.minimum(pos, max(len(g_uniq) - 1, 0))
    found = (g_uniq[pos_c] == w_sorted) if len(g_uniq) else \
        np.zeros(len(w_sorted), dtype=bool)
    numbers["missing"] = int(np.count_nonzero(~found))
    numbers["unexpected"] = int(len(g_uniq) - np.count_nonzero(found))
    rows_g = g_rows[pos_c[found]]
    rows_w = w_order[found]
    for col in want:
        if col in ("key", "wid") or col.startswith("_"):
            continue
        numbers[f"wrong.{col}"] = int(np.count_nonzero(
            got[col][rows_g] != want[col][rows_w]))
    return numbers, (rows_g, rows_w, ~found[np.argsort(w_order)])


def verdict(numbers, limits=None):
    """Lines ``name value limit`` for the run's output, and whether every
    number is within its limit (0 unless ``limits`` says otherwise)."""
    limits = limits or {}
    ok = True
    lines = []
    for name, value in numbers.items():
        limit = limits.get(name, 0)
        ok = ok and value <= limit
        lines.append(f"check {name} = {value} (limit {limit})")
    return ok, lines
