"""Plain reference of the ``pipe_cb`` deployment: the stream and its answers.

numpy only; nothing of the program is imported.  The stream is a function of
``(seed, event index)``: event ``i`` has key ``i % n_keys``, id ``i // n_keys``
and a value from one fixed draw of ``template_events`` values, rotated by the
seed and repeating every ``template_events`` events.  The deployment maps ``v -> v*3+1``, keeps ``v % 5 != 0`` and sums
count-based sliding windows over the SURVIVING tuples of each key (the
pipeline renumbers them densely in front of a window farm); at end of stream
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
    number of rows.  Every seed so carries the same values in another phase:
    different window sums, the same work (seeds that drew their own values
    differed by 12% in throughput on the chip, PERF.md PR 23)."""
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


def _map(cfg, v):
    mul, add = cfg["shapes"]["map"]
    return v * int(mul) + int(add)


def _keep(cfg, v):
    return v % int(cfg["shapes"]["filter_drop_multiple_of"]) != 0


def expected(cfg, seed, log, acc_dtype=np.int64):
    """Every window result of the stream the log describes, as columns
    ``key, wid, value, ts`` sorted by ``(key, wid)``, and ``_closes_at_us``:
    the event time of the event that completes the window (compared with
    nothing; it tells which results are due while the stream runs on).  ``acc_dtype`` narrower
    than int64 is the lower-precision control: the sum wraps as a narrower
    accumulator would."""
    shp = cfg["shapes"]
    n_keys, win, slide = int(shp["n_keys"]), int(shp["win"]), int(shp["slide"])
    period = period_events(cfg)
    chunk = int(log["chunk"])
    base_us = np.asarray(log["base_us"], dtype=np.int64)
    off_us = np.asarray(log["off_us"], dtype=np.int64)
    total = len(base_us) * chunk
    if period % n_keys or chunk % n_keys:
        raise ValueError("period and chunk must hold every key equally often")
    rows_pp = period // n_keys            # rows of one key in one period
    rows_total = total // n_keys          # rows of one key in the stream
    mapped = _map(cfg, _values(cfg, seed)).reshape(rows_pp, n_keys)
    kept = _keep(cfg, mapped)
    out = {"key": [], "wid": [], "value": [], "ts": [], "_closes_at_us": []}
    full, rem = divmod(rows_total, rows_pp)
    for k in range(n_keys):
        idx = np.flatnonzero(kept[:, k])          # surviving rows in a period
        s = len(idx)
        n = full * s + int(np.searchsorted(idx, rem))
        if n == 0:
            continue
        c = np.concatenate([[0], np.cumsum(mapped[idx, k])])
        n_wins = (n - 1) // slide + 1
        starts = np.arange(n_wins, dtype=np.int64) * slide
        ends = np.minimum(starts + win, n)

        def prefix(pos):
            return (pos // s) * c[s] + c[pos % s]

        value = prefix(ends) - prefix(starts)
        last = ends - 1                           # last surviving position
        row = (last // s) * rows_pp + idx[last % s]
        g = row * n_keys + k                      # event index
        out["key"].append(np.full(n_wins, k, dtype=np.int64))
        out["wid"].append(np.arange(n_wins, dtype=np.int64))
        out["value"].append(value)
        ts = base_us[g // chunk] + off_us[g % chunk]
        out["ts"].append(ts)
        # a full window is closed by its last event; a partial one only by
        # the end of the stream
        out["_closes_at_us"].append(np.where(starts + win <= n, ts, NEVER))
    res = {f: (np.concatenate(v) if v else np.zeros(0, np.int64))
           for f, v in out.items()}
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
    rows = []
    for j, base in enumerate(log["base_us"]):
        for e in range(chunk):
            i = j * chunk + e
            v = int(_values(cfg, seed)[i % period])
            rows.append((i % n_keys, _map(cfg, v),
                         int(base) + int(log["off_us"][e])))
    out = {"key": [], "wid": [], "value": [], "ts": []}
    for k in range(n_keys):
        surv = [(v, ts) for (kk, v, ts) in rows
                if kk == k and v % int(shp["filter_drop_multiple_of"]) != 0]
        w = 0
        while w * slide < len(surv):
            part = surv[w * slide:w * slide + win]
            out["key"].append(k)
            out["wid"].append(w)
            out["value"].append(sum(v for v, _ in part))
            out["ts"].append(part[-1][1])
            w += 1
    return {f: np.asarray(v, dtype=np.int64) for f, v in out.items()}


def events_of_missing(cfg, n_missing_windows):
    """Events whose result never arrived, for ``failed``: each window result
    is owed to ``slide`` new surviving events."""
    return n_missing_windows * int(cfg["shapes"]["slide"])
