"""Plain reference of the ``q8_new_users`` deployment: the stream and its
answers.

numpy only; nothing of the program is imported.  The stream is NEXMark's as
Apache Beam's generator makes it, the one ``q7_highest_bid`` and
``q5_hot_items`` run (that reference's ``_period`` holds the bids'
arithmetic, and ``_last_person`` / ``_last_auction`` the ids in flight):
event ``n`` is a person if ``n % 50 < 1``, an auction if ``n % 50 < 4``, else
a bid.  This one adds what Query 8 reads (``PersonGenerator``,
``AuctionGenerator``): a person's ``id`` is ``lastBase0PersonId + 1000``; an
auction's ``id`` is ``lastBase0AuctionId + 1000``, its ``seller`` the hot
person (``lastBase0PersonId / 100 * 100``) with probability 3/4 and else
uniform over the last 1,000 persons and the 10 ids ahead of them, its
``reserve`` its initial bid plus a second price (each ``round(100 *
10^(6u))``).  One draw per seed over one period of ``template_events``
events, repeated; person ids, sellers and auction ids run on from cycle to
cycle as the event ids do (``run_on``: the generator of the harness runs only
``id`` on, so the configuration's builder passes every chunk through it).

Query 8, "Monitor New Users": persons keyed by ``id`` and auctions keyed by
``seller``, both in tumbling event-time windows of ``win_us``; for a key with
a person in the window, one result per auction of the window, carrying the
person's id, the auction's reserve and (for the harness) the later of the two
event times.  The result table is unique by ``(key, wid)`` with ``key`` the
auction's id.  Event times come from the generator's log (one per chunk), so
the answers are a function of ``(seed, log)``.
"""

from __future__ import annotations

import numpy as np

from . import q7_highest_bid_oracle as _nexmark

NEVER = np.iinfo(np.int64).max
PERSON, AUCTION, BID = _nexmark.PERSON, _nexmark.AUCTION, _nexmark.BID
_HOT_BATCH = 100          # AuctionGenerator.HOT_SELLER_RATIO
_ID_LEAD = 10             # PERSON_ID_LEAD
_COLS = {}
_PLACES = {}


def period_events(cfg):
    return int(cfg["stream"]["template_events"])


def id_shift(cfg, n_events):
    return n_events


def _period(cfg, seed):
    """The columns of one whole period (cached: one seed at a time): the
    NEXMark cells' own, and ``person``, ``seller``, ``reserve``; an auction
    row carries its id in ``auction``."""
    period = period_events(cfg)
    key = (period, int(seed))
    if key in _COLS:
        return _COLS[key]
    _COLS.clear()
    shp = cfg["shapes"]
    base = _nexmark._period(cfg, seed)
    etype = base["event_type"]
    n = np.arange(period, dtype=np.int64)
    rng = np.random.default_rng([abs(int(seed)), 8])
    last_p = _nexmark._last_person(shp, n)
    hot = rng.integers(0, int(shp["hot_sellers_ratio"]), period) > 0
    active = np.minimum(last_p + 1, int(shp["num_active_people"]))
    pick = last_p + 1 - active + (rng.random(period)
                                  * (active + _ID_LEAD)).astype(np.int64)
    seller = np.where(hot, (last_p // _HOT_BATCH) * _HOT_BATCH, pick) \
        + int(shp["first_person_id"])

    def price():
        return np.rint(np.power(10.0, rng.random(period) * 6.0)
                       * 100.0).astype(np.int64)

    reserve = price() + price()
    is_p, is_a = etype == PERSON, etype == AUCTION
    zero = np.int64(0)
    _COLS[key] = dict(
        base,
        auction=np.where(is_a, _nexmark._last_auction(shp, n)
                         + int(shp["first_auction_id"]), base["auction"]),
        person=np.where(is_p, last_p + int(shp["first_person_id"]), zero),
        seller=np.where(is_a, seller, zero),
        reserve=np.where(is_a, reserve, zero))
    return _COLS[key]


def per_period(cfg):
    """``(persons, auctions)`` one period of the stream opens: what person
    ids with sellers, and auction ids, run on by."""
    shp = cfg["shapes"]
    den, per, auc = (int(shp[k]) for k in
                     ("proportion_denominator", "person_proportion",
                      "auction_proportion"))
    epochs, rest = divmod(period_events(cfg), den)
    return (epochs * per + min(rest, per),
            epochs * auc + min(max(rest - per, 0), auc))


def columns(cfg, seed, start, n):
    """Columns of events ``start .. start+n-1`` (inside one period)."""
    out = {"key": np.zeros(n, dtype=np.int64),
           "id": np.arange(start, start + n, dtype=np.int64)}
    for name, col in _period(cfg, seed).items():
        out[name] = col[start:start + n]
    return out


def run_on(cfg, batch):
    """Person ids, sellers and auction ids run on: adds the chunk's cycle
    times the period's persons to ``person`` of its person rows and to
    ``seller`` of its auction rows, and times the period's auctions to
    ``auction`` of its auction rows, in place (a chunk lies inside one cycle;
    its ids have run on already; a bid's auction and bidder are left as they
    are: Query 8 drops bids)."""
    period = period_events(cfg)
    cycle, phase = divmod(int(batch["id"][0]), period)
    if not cycle:
        return batch
    n_p, n_a = per_period(cfg)
    place = (period, phase, len(batch))
    if place not in _PLACES:
        if len(_PLACES) > 256:
            _PLACES.clear()
        etype = batch["event_type"]
        _PLACES[place] = (np.flatnonzero(etype == PERSON),
                          np.flatnonzero(etype == AUCTION))
    persons, auctions = _PLACES[place]
    batch["person"][persons] += cycle * n_p
    batch["seller"][auctions] += cycle * n_p
    batch["auction"][auctions] += cycle * n_a
    return batch


def _one_time_a_chunk(log):
    if np.any(np.asarray(log["off_us"])) or log.get("own_ts"):
        raise ValueError("this reference is written for chunks that carry "
                         "one event time each (the closed loop)")


def _table(cols, closes=None):
    names = ("key", "wid", "person", "reserve", "ts")
    out = {n: (np.concatenate(c) if c else np.zeros(0, dtype=np.int64))
           for n, c in zip(names, cols)}
    if closes is not None:
        out["_closes_at_us"] = (np.concatenate(closes) if closes
                                else np.zeros(0, dtype=np.int64))
    return out


def expected(cfg, seed, log, acc_dtype=np.int64):
    """Every result, as columns ``key`` (the auction's id), ``wid``,
    ``person``, ``reserve``, ``ts`` in (window, arrival) order, which is
    ``(key, wid)`` order too, and ``_closes_at_us``: the event time of the
    first chunk at or past the window's end, which closes it (compared with
    nothing; it tells which results are due while the stream runs on).
    ``acc_dtype=np.int16`` is the lower-precision control: the join key of
    both sides narrowed to int16, one width under the int32 the device holds
    it in, so that persons collide and auctions meet persons that are not
    their sellers."""
    _one_time_a_chunk(log)
    win_us = int(cfg["shapes"]["win_us"])
    chunk, period = int(log["chunk"]), period_events(cfg)
    base_us = np.asarray(log["base_us"], dtype=np.int64)
    cols = _period(cfg, seed)
    n_p, n_a = per_period(cfg)
    cache = {}

    def part(phase):
        if phase not in cache:
            sl = slice(phase, phase + chunk)
            etype = cols["event_type"][sl]
            p, a = etype == PERSON, etype == AUCTION
            cache[phase] = (cols["person"][sl][p], cols["auction"][sl][a],
                            cols["seller"][sl][a], cols["reserve"][sl][a])
        return cache[phase]

    narrow = np.dtype(acc_dtype) != np.int64
    wids = base_us // win_us
    out, closes = [[] for _ in range(5)], []
    for wid in np.unique(wids):
        lo, hi = np.searchsorted(wids, [wid, wid + 1])
        parts = []
        for j in range(lo, hi):
            cycle, phase = divmod(j * chunk, period)
            p, a, s, r = part(phase)
            parts.append((p + cycle * n_p, a + cycle * n_a, s + cycle * n_p,
                          r))
        persons, auctions, sellers, reserves = (
            np.concatenate([x[i] for x in parts]) for i in range(4))
        if not len(persons) or not len(auctions):
            continue
        # one event time a chunk
        p_ts = np.repeat(base_us[lo:hi], [len(x[0]) for x in parts])
        a_ts = np.repeat(base_us[lo:hi], [len(x[1]) for x in parts])
        p_key, s_key = persons, sellers
        if narrow:
            p_key = persons.astype(acc_dtype).astype(np.int64)
            s_key = sellers.astype(acc_dtype).astype(np.int64)
        order = np.argsort(p_key, kind="stable")
        p_sorted = p_key[order]
        at = np.minimum(np.searchsorted(p_sorted, s_key), len(p_sorted) - 1)
        found = p_sorted[at] == s_key
        who = order[at[found]]
        out[0].append(auctions[found])
        out[1].append(np.full(len(who), wid))
        out[2].append(persons[who])
        out[3].append(reserves[found])
        out[4].append(np.maximum(a_ts[found], p_ts[who]))
        j = int(np.searchsorted(base_us, (wid + 1) * win_us))
        closes.append(np.full(len(who), NEVER if j >= len(base_us)
                              else base_us[j]))
    return _table(out, closes)


def brute_force(cfg, seed, log):
    """The same answers by a loop over every event: a dictionary of each
    window's persons, then a loop over its auctions (tests only)."""
    _one_time_a_chunk(log)
    win_us = int(cfg["shapes"]["win_us"])
    chunk, period = int(log["chunk"]), period_events(cfg)
    cols = _period(cfg, seed)
    n_p, n_a = per_period(cfg)
    persons, auctions = {}, {}
    for j, base in enumerate(log["base_us"]):
        ts = int(base)
        wid = ts // win_us
        for e in range(chunk):
            cycle, p = divmod(j * chunk + e, period)
            if cols["event_type"][p] == PERSON:
                persons.setdefault(wid, {})[
                    int(cols["person"][p]) + cycle * n_p] = ts
            elif cols["event_type"][p] == AUCTION:
                auctions.setdefault(wid, []).append(
                    (int(cols["auction"][p]) + cycle * n_a,
                     int(cols["seller"][p]) + cycle * n_p,
                     int(cols["reserve"][p]), ts))
    rows = []
    for wid in sorted(auctions):
        new = persons.get(wid, {})
        for auction, seller, reserve, ts in auctions[wid]:
            if seller in new:
                rows.append((auction, wid, seller, reserve,
                             max(ts, new[seller])))
    return _table([[np.asarray([r[i] for r in rows], dtype=np.int64)]
                   for i in range(5)])


def events_of_missing(cfg, n_missing_results):
    """Events whose result never arrived, for ``failed``: a missing result
    stands for one auction."""
    return n_missing_results
