"""Plain reference of the ``sum_cb`` deployment: the stream and its answers.

numpy only; nothing of the program is imported.  The stream is a function of
``(seed, event index)``: event ``i`` has key ``i % n_keys``, id ``i // n_keys``
and a value from one fixed draw of ``template_events`` values, rotated by the
seed and repeating every ``template_events`` events.  The deployment sums
count-based sliding windows over the tuples of each key; at end of stream
every window that holds a tuple is flushed partial.

``expected`` uses per-period prefix sums, so a long window costs no more than
a short one; ``benchmarks/tests`` checks it against ``brute_force``.
"""

from __future__ import annotations

import numpy as np

_VALUES = {}
NEVER = np.iinfo(np.int64).max


def period_events(cfg):
    return int(cfg["stream"]["template_events"])


def id_shift(cfg, n_events):
    """What to add to ``id`` after ``n_events`` events (whole periods)."""
    return n_events // int(cfg["shapes"]["n_keys"])


def _values(cfg, seed):
    """One period of values as the seed gives them: ONE draw, the same for
    every seed (``stream.draw_seed``), which the seed rotates by a whole
    number of rows, so that every seed carries the same work."""
    period, n_keys = period_events(cfg), int(cfg["shapes"]["n_keys"])
    rot = (abs(int(seed)) % (period // n_keys)) * n_keys
    key = (period, int(cfg["stream"]["draw_seed"]), rot)
    if key not in _VALUES:
        _VALUES.clear()
        lo, hi = cfg["shapes"]["value_range"]
        rng = np.random.default_rng(key[1])
        base = rng.integers(lo, hi, size=period).astype(np.int64)
        _VALUES[key] = np.roll(base, -rot)
    return _VALUES[key]


def columns(cfg, seed, start, n):
    """Columns of events ``start .. start+n-1`` (inside one period)."""
    n_keys = int(cfg["shapes"]["n_keys"])
    i = np.arange(start, start + n, dtype=np.int64)
    return {"key": i % n_keys, "id": i // n_keys,
            "value": _values(cfg, seed)[start:start + n]}


def expected(cfg, seed, log, acc_dtype=np.int64):
    """Every window result of the stream the log describes, as columns
    ``key, wid, value, ts`` sorted by ``(key, wid)``, and ``_closes_at_us``:
    the event time of the event that completes the window (compared with
    nothing; it tells which results are due while the stream runs on).
    ``acc_dtype`` narrower than int64 is the lower-precision control: the sum
    wraps as a narrower accumulator would."""
    shp = cfg["shapes"]
    n_keys, win, slide = int(shp["n_keys"]), int(shp["win"]), int(shp["slide"])
    period = period_events(cfg)
    chunk = int(log["chunk"])
    base_us = np.asarray(log["base_us"], dtype=np.int64)
    off_us = np.asarray(log["off_us"], dtype=np.int64)
    if period % n_keys or chunk % n_keys:
        raise ValueError("period and chunk must hold every key equally often")
    rows_pp = period // n_keys            # rows of one key in one period
    n = len(base_us) * chunk // n_keys    # rows of one key in the stream
    if n == 0:
        return {f: np.zeros(0, np.int64)
                for f in ("key", "wid", "value", "ts", "_closes_at_us")}
    by_key = _values(cfg, seed).reshape(rows_pp, n_keys)
    # c[p, k]: sum of key k's first p rows of a period
    c = np.zeros((rows_pp + 1, n_keys), dtype=np.int64)
    np.cumsum(by_key, axis=0, out=c[1:])
    n_wins = (n - 1) // slide + 1
    starts = np.arange(n_wins, dtype=np.int64) * slide
    ends = np.minimum(starts + win, n)

    def prefix(pos):
        return (pos // rows_pp)[:, None] * c[rows_pp] + c[pos % rows_pp]

    value = prefix(ends) - prefix(starts)                 # (n_wins, n_keys)
    g = (ends - 1)[:, None] * n_keys + np.arange(n_keys)  # last event's index
    ts = base_us[g // chunk] + off_us[g % chunk]
    # a full window is closed by its last event; a partial one only by the
    # end of the stream
    closes = np.where((starts + win <= n)[:, None], ts, NEVER)
    res = {"key": np.tile(np.arange(n_keys, dtype=np.int64), (n_wins, 1)),
           "wid": np.tile(np.arange(n_wins, dtype=np.int64)[:, None],
                          (1, n_keys)),
           "value": value, "ts": ts, "_closes_at_us": closes}
    res = {f: v.T.reshape(-1) for f, v in res.items()}    # by (key, wid)
    if np.dtype(acc_dtype) != np.int64:
        res["value"] = res["value"].astype(acc_dtype).astype(np.int64)
    return res


def brute_force(cfg, seed, log):
    """The same answers by a per-window loop over the materialised stream
    (tests only: small sizes)."""
    shp = cfg["shapes"]
    n_keys, win, slide = int(shp["n_keys"]), int(shp["win"]), int(shp["slide"])
    period = period_events(cfg)
    chunk = int(log["chunk"])
    rows = [[] for _ in range(n_keys)]
    for j, base in enumerate(log["base_us"]):
        for e in range(chunk):
            i = j * chunk + e
            rows[i % n_keys].append((int(_values(cfg, seed)[i % period]),
                                     int(base) + int(log["off_us"][e])))
    out = {"key": [], "wid": [], "value": [], "ts": []}
    for k in range(n_keys):
        w = 0
        while w * slide < len(rows[k]):
            part = rows[k][w * slide:w * slide + win]
            out["key"].append(k)
            out["wid"].append(w)
            out["value"].append(sum(v for v, _ in part))
            out["ts"].append(part[-1][1])
            w += 1
    return {f: np.asarray(v, dtype=np.int64) for f, v in out.items()}


def events_of_missing(cfg, n_missing_windows):
    """Events whose result never arrived, for ``failed``: each window result
    is owed to ``slide`` new events of its key."""
    return n_missing_windows * int(cfg["shapes"]["slide"])
