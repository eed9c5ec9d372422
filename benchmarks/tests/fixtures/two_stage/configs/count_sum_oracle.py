"""Plain reference of the fixture ``count_sum``: per key, the number of
tuples in each count-based sliding window (partial ones at the end of the
stream included), then the sum of those counts over count-based sliding
windows of the first stage's results.  numpy only."""

from __future__ import annotations

import numpy as np

NEVER = np.iinfo(np.int64).max


def period_events(cfg):
    return int(cfg["stream"]["template_events"])


def id_shift(cfg, n_events):
    return n_events // int(cfg["shapes"]["n_keys"])


def columns(cfg, seed, start, n):
    n_keys = int(cfg["shapes"]["n_keys"])
    i = np.arange(start, start + n, dtype=np.int64)
    return {"key": i % n_keys, "id": i // n_keys,
            "value": (i * 31 + abs(int(seed))) % 100}


def expected(cfg, seed, log, acc_dtype=np.int64):
    shp = cfg["shapes"]
    n_keys = int(shp["n_keys"])
    w1, s1 = int(shp["count_win"]), int(shp["count_slide"])
    w2, s2 = int(shp["sum_win"]), int(shp["sum_slide"])
    chunk = int(log["chunk"])
    base_us = np.asarray(log["base_us"], dtype=np.int64)
    off_us = np.asarray(log["off_us"], dtype=np.int64)
    n = len(base_us) * chunk // n_keys         # rows of one key
    names = ("key", "wid", "total", "ts", "_closes_at_us")
    if n == 0:
        return {f: np.zeros(0, np.int64) for f in names}
    m = (n - 1) // s1 + 1                      # first-stage results a key
    first = np.arange(m, dtype=np.int64) * s1
    counts = np.minimum(first + w1, n) - first
    last_row = np.minimum(first + w1, n) - 1   # the row that ends each
    whole = first + w1 <= n
    c = np.concatenate([[0], np.cumsum(counts)])
    m2 = (m - 1) // s2 + 1
    lo = np.arange(m2, dtype=np.int64) * s2
    hi = np.minimum(lo + w2, m)
    total = (c[hi] - c[lo]).astype(acc_dtype).astype(np.int64)
    out = {f: [] for f in names}
    for k in range(n_keys):
        g = last_row[hi - 1] * n_keys + k      # the last event's index
        ts = base_us[g // chunk] + off_us[g % chunk]
        out["key"].append(np.full(m2, k, dtype=np.int64))
        out["wid"].append(np.arange(m2, dtype=np.int64))
        out["total"].append(total)
        out["ts"].append(ts)
        # closed by an event only where both windows are whole; the rest
        # wait for the end of the stream
        out["_closes_at_us"].append(
            np.where((lo + w2 <= m) & whole[hi - 1], ts, NEVER))
    return {f: np.concatenate(v) for f, v in out.items()}


def brute_force(cfg, seed, log):
    shp = cfg["shapes"]
    n_keys = int(shp["n_keys"])
    w1, s1 = int(shp["count_win"]), int(shp["count_slide"])
    w2, s2 = int(shp["sum_win"]), int(shp["sum_slide"])
    chunk = int(log["chunk"])
    rows = [[] for _ in range(n_keys)]
    for j, base in enumerate(log["base_us"]):
        for e in range(chunk):
            rows[(j * chunk + e) % n_keys].append(
                int(base) + int(log["off_us"][e]))
    out = {"key": [], "wid": [], "total": [], "ts": []}
    for k in range(n_keys):
        stage1, w = [], 0
        while w * s1 < len(rows[k]):
            part = rows[k][w * s1:w * s1 + w1]
            stage1.append((len(part), part[-1]))
            w += 1
        v = 0
        while v * s2 < len(stage1):
            part = stage1[v * s2:v * s2 + w2]
            out["key"].append(k)
            out["wid"].append(v)
            out["total"].append(sum(cnt for cnt, _ in part))
            out["ts"].append(part[-1][1])
            v += 1
    return {f: np.asarray(v, dtype=np.int64) for f, v in out.items()}


def events_of_missing(cfg, n_missing_windows):
    shp = cfg["shapes"]
    return n_missing_windows * int(shp["count_slide"]) * int(shp["sum_slide"])
