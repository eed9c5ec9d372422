"""Plain reference of the ``q7_highest_bid`` deployment: the stream and its
answers.

numpy only; nothing of the program is imported.  The stream is NEXMark's as
Apache Beam's generator makes it (``sdks/java/testing/nexmark``
``GeneratorConfig``, ``BidGenerator``, ``AuctionGenerator``,
``PersonGenerator``, ``PriceGenerator``): event ``n`` is a person if
``n % 50 < 1``, an auction if ``n % 50 < 4``, else a bid; a bid's price is
``round(100 * 10^(6u))``, its auction the hot one
(``lastBase0AuctionId / 100 * 100``) with probability 1/2 and else uniform
over the auctions in flight, its bidder the hot one with probability 3/4 and
else uniform over the active people.  The random numbers are one draw per
seed over one period of ``template_events`` events; the stream repeats that
period with ids running on.

Query 7, "Highest Bid": per tumbling event-time window, the bid with the
highest price (ties to the lowest id), the number of bids and the time of the
last one.  Windows without a bid give no result.  Event times come from the
generator's log (a base per chunk plus a per-event offset), so the answers are
a function of ``(seed, log)``.
"""

from __future__ import annotations

import numpy as np

NEVER = np.iinfo(np.int64).max

PERSON, AUCTION, BID = 0, 1, 2
_HOT_BATCH = 100          # BidGenerator.HOT_AUCTION_RATIO / HOT_BIDDER_RATIO
_ID_LEAD = 10             # AUCTION_ID_LEAD, PERSON_ID_LEAD
_COLS = {}


def period_events(cfg):
    return int(cfg["stream"]["template_events"])


def id_shift(cfg, n_events):
    return n_events


def _last_auction(shp, n):
    """``AuctionGenerator.lastBase0AuctionId`` of event ``n``."""
    den, per, auc = (int(shp[k]) for k in
                     ("proportion_denominator", "person_proportion",
                      "auction_proportion"))
    epoch, off = n // den, n % den
    before = off < per
    epoch = np.where(before, epoch - 1, epoch)
    off = np.where(before | (off >= per + auc), auc - 1, off - per)
    return epoch * auc + off


def _last_person(shp, n):
    """``PersonGenerator.lastBase0PersonId`` of event ``n``."""
    den, per = int(shp["proportion_denominator"]), int(shp["person_proportion"])
    return (n // den) * per + np.minimum(n % den, per - 1)


def _period(cfg, seed):
    """The columns of one whole period (cached: one seed at a time)."""
    period = period_events(cfg)
    key = (period, int(seed))
    if key in _COLS:
        return _COLS[key]
    _COLS.clear()
    shp = cfg["shapes"]
    n = np.arange(period, dtype=np.int64)
    den, per, auc = (int(shp[k]) for k in
                     ("proportion_denominator", "person_proportion",
                      "auction_proportion"))
    rem = n % den
    etype = np.where(rem < per, PERSON,
                     np.where(rem < per + auc, AUCTION, BID)).astype(np.int8)
    rng = np.random.default_rng([abs(int(seed)), 7])
    bid = etype == BID
    # price: round(10^(6u) * 100)
    price = np.rint(np.power(10.0, rng.random(period) * 6.0)
                    * 100.0).astype(np.int64)
    # auction
    last_a = _last_auction(shp, n)
    hot_a = rng.integers(0, int(shp["hot_auction_ratio"]), period) > 0
    min_a = np.maximum(last_a - int(shp["num_in_flight_auctions"]), 0)
    pick_a = min_a + (rng.random(period)
                      * (last_a - min_a + 1 + _ID_LEAD)).astype(np.int64)
    auction = np.where(hot_a, (last_a // _HOT_BATCH) * _HOT_BATCH, pick_a) \
        + int(shp["first_auction_id"])
    # bidder
    last_p = _last_person(shp, n)
    hot_p = rng.integers(0, int(shp["hot_bidders_ratio"]), period) > 0
    active = np.minimum(last_p + 1, int(shp["num_active_people"]))
    pick_p = last_p + 1 - active + (rng.random(period)
                                    * (active + _ID_LEAD)).astype(np.int64)
    bidder = np.where(hot_p, (last_p // _HOT_BATCH) * _HOT_BATCH + 1,
                      pick_p) + int(shp["first_person_id"])
    zero = np.int64(0)
    _COLS[key] = {"event_type": etype,
                  "auction": np.where(bid, auction, zero),
                  "bidder": np.where(bid, bidder, zero),
                  "price": np.where(bid, price, zero)}
    return _COLS[key]


def columns(cfg, seed, start, n):
    """Columns of events ``start .. start+n-1`` (inside one period): persons
    and auctions carry their event type and padding only."""
    cols = _period(cfg, seed)
    out = {"key": np.zeros(n, dtype=np.int64),
           "id": np.arange(start, start + n, dtype=np.int64)}
    for name, col in cols.items():
        out[name] = col[start:start + n]
    return out


def _closing_times(base_us, off_us, ends_us):
    """Event time of the first event at or past each window end (every chunk
    carries bids for every MAP worker, so that event closes the window), or
    NEVER where the stream ends first."""
    last_ts = base_us + off_us[-1] if len(base_us) else base_us
    out = []
    for end_us in ends_us:
        j = int(np.searchsorted(last_ts, end_us))
        out.append(NEVER if j >= len(base_us) else int(base_us[j]) + int(
            off_us[np.searchsorted(off_us, end_us - int(base_us[j]))]))
    return out


def expected(cfg, seed, log, acc_dtype=np.int64):
    """Every window result, as columns ``key, wid, auction, bidder, price,
    dateTime, count, lastUpdate`` sorted by ``wid``, and ``_closes_at_us``
    (compared with nothing; it tells which results are due while the stream
    runs on).  ``acc_dtype=np.int16`` is the lower-precision control: prices
    compared after narrowing to int16, one width under the device's int32."""
    win_us = int(cfg["shapes"]["win_us"])
    chunk = int(log["chunk"])
    base_us = np.asarray(log["base_us"], dtype=np.int64)
    off_us = np.asarray(log["off_us"], dtype=np.int64)
    period = period_events(cfg)
    cols = _period(cfg, seed)
    cache = {}                  # phase in the period -> the chunk's bids
    best = {}                   # wid -> [price, id, auction, bidder, ts,
    #                                     count, last]

    def offer(w, price, bid_id, auction, bidder, ts, count, last):
        cur = best.get(w)
        if cur is None:
            best[w] = [price, bid_id, auction, bidder, ts, count, last]
            return
        if price > cur[0] or (price == cur[0] and bid_id < cur[1]):
            cur[:5] = [price, bid_id, auction, bidder, ts]
        cur[5] += count
        cur[6] = max(cur[6], last)

    def top(price):
        """Index of the highest price: the first, so the lowest id on ties."""
        return int(np.flatnonzero(price == price.max())[0])

    for j, base in enumerate(base_us):
        phase = (j * chunk) % period
        if phase not in cache:
            sl = slice(phase, phase + chunk)
            pos = np.flatnonzero(cols["event_type"][sl] == BID)
            price = cols["price"][sl][pos].astype(acc_dtype).astype(np.int64)
            cache[phase] = (pos, price, cols["auction"][sl][pos],
                            cols["bidder"][sl][pos],
                            top(price) if len(pos) else -1)
        pos, price, auction, bidder, k_all = cache[phase]
        if not len(pos):
            continue
        base = int(base)
        ts = base + off_us[pos]
        w_first, w_last = int(ts[0]) // win_us, int(ts[-1]) // win_us
        if w_first == w_last:
            k = k_all
            offer(w_first, int(price[k]), j * chunk + int(pos[k]),
                  int(auction[k]), int(bidder[k]), int(ts[k]), len(pos),
                  int(ts[-1]))
            continue
        wids = ts // win_us
        for w in np.unique(wids):
            m = np.flatnonzero(wids == w)
            k = m[top(price[m])]
            offer(int(w), int(price[k]), j * chunk + int(pos[k]),
                  int(auction[k]), int(bidder[k]), int(ts[k]), len(m),
                  int(ts[m[-1]]))
    wids = sorted(best)
    closes = _closing_times(base_us, off_us, [(w + 1) * win_us for w in wids])
    rows = [best[w] for w in wids]

    def col(i):
        return np.asarray([r[i] for r in rows], dtype=np.int64)

    return {"key": np.zeros(len(wids), dtype=np.int64),
            "wid": np.asarray(wids, dtype=np.int64),
            "auction": col(2), "bidder": col(3), "price": col(0),
            "dateTime": col(4), "count": col(5), "lastUpdate": col(6),
            "_closes_at_us": np.asarray(closes, dtype=np.int64)}


def brute_force(cfg, seed, log):
    """The same answers by a loop over every event (tests only)."""
    win_us = int(cfg["shapes"]["win_us"])
    chunk = int(log["chunk"])
    period = period_events(cfg)
    cols = _period(cfg, seed)
    table = {}
    for j, base in enumerate(log["base_us"]):
        for e in range(chunk):
            i = j * chunk + e
            p = i % period
            if cols["event_type"][p] != BID:
                continue
            ts = int(base) + int(log["off_us"][e])
            w = ts // win_us
            price = int(cols["price"][p])
            cur = table.get(w)
            if cur is None:
                cur = table[w] = [-1, 0, 0, 0, 0, -1]
            if price > cur[0]:          # ids ascend: the first one stays
                cur[:4] = [price, int(cols["auction"][p]),
                           int(cols["bidder"][p]), ts]
            cur[4] += 1
            cur[5] = max(cur[5], ts)
    wids = sorted(table)

    def col(i):
        return np.asarray([table[w][i] for w in wids], dtype=np.int64)

    return {"key": np.zeros(len(wids), dtype=np.int64),
            "wid": np.asarray(wids, dtype=np.int64), "auction": col(1),
            "bidder": col(2), "price": col(0), "dateTime": col(3),
            "count": col(4), "lastUpdate": col(5)}


def events_of_missing(cfg, n_missing_windows):
    """Events whose result never arrived, for ``failed``: a missing result
    stands for at least one bid."""
    return n_missing_windows
