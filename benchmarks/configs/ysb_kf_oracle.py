"""Plain reference of the ``ysb_kf`` deployment: the stream and its answers.

numpy only; nothing of the program is imported.  The stream is the Yahoo
Streaming Benchmark's as WindFlow generates it: event ``v`` (the event index,
offset by the seed) has ``ad_id = (v % 100000) % n_ads``,
``event_type = (v % 100000) % 3`` and, this repo's extension,
``revenue = (v % 100000) % 97 + 1``.  The deployment keeps the views
(``event_type == 0``), joins each ad to its campaign (``ad_id // ads_per
campaign``) and, per campaign and tumbling event-time window, gives COUNT,
MAX(ts) and SUM(revenue).  Windows without a view give no result.

Event times come from the generator's log (a base per chunk plus a per-event
offset), so the answers are a function of ``(seed, log)``.
"""

from __future__ import annotations

import numpy as np

NEVER = np.iinfo(np.int64).max


def period_events(cfg):
    return int(cfg["stream"]["recurrence_period"])


def id_shift(cfg, n_events):
    return n_events


def _first_index(cfg, seed):
    return abs(int(seed)) % period_events(cfg)


def columns(cfg, seed, start, n):
    shp = cfg["shapes"]
    n_ads = int(shp["n_campaigns"]) * int(shp["ads_per_campaign"])
    v = _first_index(cfg, seed) + np.arange(start, start + n, dtype=np.int64)
    vm = v % period_events(cfg)
    return {"key": np.zeros(n, dtype=np.int64), "id": v, "ad_id": vm % n_ads,
            "event_type": (vm % int(shp["event_types"])).astype(np.int8),
            "revenue": vm % int(shp["revenue_modulus"]) + 1}


def _views(cfg, seed, start, n):
    """Campaign and revenue of the views among events start..start+n-1, and
    their positions."""
    cols = columns(cfg, seed, start, n)
    pos = np.flatnonzero(cols["event_type"] == int(cfg["shapes"]["view_type"]))
    cmp_ = cols["ad_id"][pos] // int(cfg["shapes"]["ads_per_campaign"])
    return pos, cmp_, cols["revenue"][pos]


def expected(cfg, seed, log, acc_dtype=np.int64):
    """Every window result, as columns ``key, wid, count, lastUpdate,
    revenue`` sorted by ``(key, wid)``, and ``_closes_at_us``: the event time
    of the event that closes the window (compared with nothing; it tells which
    results are due while the stream runs on).  ``acc_dtype=np.int16`` is the
    lower-precision control: revenue accumulated chunk by chunk in int16."""
    n_cmp = int(cfg["shapes"]["n_campaigns"])
    win_us = int(cfg["shapes"]["win_us"])
    chunk = int(log["chunk"])
    base_us = np.asarray(log["base_us"], dtype=np.int64)
    off_us = np.asarray(log["off_us"], dtype=np.int64)
    period = period_events(cfg)
    acc = {}                    # wid -> [count, last, revenue] per campaign
    cache = {}                  # chunk's phase in the period -> its views

    def bucket(w):
        if w not in acc:
            acc[w] = [np.zeros(n_cmp, np.int64), np.full(n_cmp, -1, np.int64),
                      np.zeros(n_cmp, dtype=acc_dtype)]
        return acc[w]

    for j, base in enumerate(base_us):
        phase = (j * chunk) % period
        if phase not in cache:
            pos, cmp_, rev = _views(cfg, seed, j * chunk, chunk)
            max_off = np.full(n_cmp, -1, np.int64)
            np.maximum.at(max_off, cmp_, off_us[pos])
            cache[phase] = (pos, cmp_, rev, max_off,
                            np.bincount(cmp_, minlength=n_cmp),
                            np.bincount(cmp_, weights=rev, minlength=n_cmp))
        pos, cmp_, rev, max_off, cnt_all, rev_all = cache[phase]
        base = int(base)
        w_first = (base + int(off_us[pos[0]])) // win_us if len(pos) else 0
        w_last = (base + int(off_us[pos[-1]])) // win_us if len(pos) else 0
        if w_first == w_last:
            # the whole chunk falls into one window: its per-campaign totals
            cnt, last, revenue = bucket(w_first)
            cnt += cnt_all
            np.maximum(last, np.where(max_off >= 0, base + max_off, -1),
                       out=last)
            revenue += rev_all.astype(acc_dtype)
            continue
        ts = base + off_us[pos]
        wids = ts // win_us
        for w in np.unique(wids):
            m = wids == w
            cnt, last, revenue = bucket(int(w))
            cnt += np.bincount(cmp_[m], minlength=n_cmp)
            np.maximum.at(last, cmp_[m], ts[m])
            revenue += np.bincount(cmp_[m], weights=rev[m],
                                   minlength=n_cmp).astype(acc_dtype)
    out = {"key": [], "wid": [], "count": [], "lastUpdate": [], "revenue": [],
           "_closes_at_us": []}
    last_ts = base_us + off_us[-1] if len(base_us) else base_us
    for w in sorted(acc):
        cnt, last, revenue = acc[w]
        live = np.flatnonzero(cnt > 0)
        # the window is closed by the first event at or past its end (every
        # chunk carries every campaign), or only by the end of the stream
        end_us = (w + 1) * win_us
        j = int(np.searchsorted(last_ts, end_us))
        closes = NEVER if j >= len(base_us) else int(base_us[j]) + int(
            off_us[np.searchsorted(off_us, end_us - int(base_us[j]))])
        out["_closes_at_us"].append(np.full(len(live), closes, np.int64))
        out["key"].append(live)
        out["wid"].append(np.full(len(live), w, dtype=np.int64))
        out["count"].append(cnt[live])
        out["lastUpdate"].append(last[live])
        out["revenue"].append(revenue[live].astype(np.int64))
    res = {f: (np.concatenate(v).astype(np.int64) if v
               else np.zeros(0, np.int64)) for f, v in out.items()}
    order = np.lexsort((res["wid"], res["key"]))
    return {f: v[order] for f, v in res.items()}


def brute_force(cfg, seed, log):
    """The same answers by a loop over every event (tests only)."""
    shp = cfg["shapes"]
    chunk = int(log["chunk"])
    period = period_events(cfg)
    n_ads = int(shp["n_campaigns"]) * int(shp["ads_per_campaign"])
    v0 = abs(int(seed)) % period
    table = {}
    for j, base in enumerate(log["base_us"]):
        for e in range(chunk):
            vm = (v0 + j * chunk + e) % period
            if vm % int(shp["event_types"]) != int(shp["view_type"]):
                continue
            ts = int(base) + int(log["off_us"][e])
            key = ((vm % n_ads) // int(shp["ads_per_campaign"]),
                   ts // int(shp["win_us"]))
            c, last, rev = table.get(key, (0, -1, 0))
            table[key] = (c + 1, max(last, ts),
                          rev + vm % int(shp["revenue_modulus"]) + 1)
    keys = sorted(table)
    return {"key": np.asarray([k for k, _ in keys], dtype=np.int64),
            "wid": np.asarray([w for _, w in keys], dtype=np.int64),
            "count": np.asarray([table[k][0] for k in keys], dtype=np.int64),
            "lastUpdate": np.asarray([table[k][1] for k in keys],
                                     dtype=np.int64),
            "revenue": np.asarray([table[k][2] for k in keys],
                                  dtype=np.int64)}


def events_of_missing(cfg, n_missing_windows):
    """Events whose result never arrived, for ``failed``: a missing result
    stands for at least one view."""
    return n_missing_windows
