"""Plain reference of the ``q5_hot_items`` deployment: the stream and its
answers.

numpy only; nothing of the program is imported.  The stream is NEXMark's as
Apache Beam's generator makes it, the same as ``q7_highest_bid``'s (that
reference's ``_period`` holds the arithmetic: event ``n`` is a person if
``n % 50 < 1``, an auction if ``n % 50 < 4``, else a bid; a bid goes to the
hot auction ``lastBase0AuctionId / 100 * 100`` with probability 1/2 and else
uniformly to the auctions in flight), one draw per seed over one period of
``template_events`` events, repeated.  Query 5 keys on the auction, so here
the auction ids run on from cycle to cycle as the event ids do (``run_on``):
a period opens 3 auctions in 50 events, and cycle ``c``'s auctions are the
template's plus ``c`` times that many.  The generator of the harness runs only
``id`` on, so the configuration's builder passes every chunk through
``run_on`` before the program sees it.

Query 5, "Hot Items": per sliding event-time window (``win_us`` every
``slide_us``) the auction with the most bids, ties to the lowest auction id,
with that count (``num``), the bids of the whole window (``bids``) and the
event time of its last bid (``lastUpdate``).  Window ``wid`` is
``[wid * slide_us, wid * slide_us + win_us)``, from 0 on: there is no window
that starts before the stream does.  An (auction, window) pair without a bid
counts nowhere; a window without a bid gives no result.  Event times come
from the generator's log, so the answers are a function of ``(seed, log)``.
"""

from __future__ import annotations

import numpy as np

from . import q7_highest_bid_oracle as _nexmark

NEVER = np.iinfo(np.int64).max
BID = _nexmark.BID


def period_events(cfg):
    return int(cfg["stream"]["template_events"])


def id_shift(cfg, n_events):
    return n_events


def auctions_per_period(cfg):
    """Auctions one period of the stream opens: what auction ids run on by."""
    shp = cfg["shapes"]
    period, den = period_events(cfg), int(shp["proportion_denominator"])
    if period % den:
        raise ValueError(f"a period of {period} events is no whole number "
                         f"of {den}-event epochs")
    return period // den * int(shp["auction_proportion"])


def columns(cfg, seed, start, n):
    """Columns of events ``start .. start+n-1`` (inside one period); a bid's
    key is its auction."""
    out = _nexmark.columns(cfg, seed, start, n)
    out["key"] = out["auction"]
    return out


def run_on(cfg, batch):
    """Auction ids run on: adds ``(id // period) x auctions_per_period`` to
    ``key`` and ``auction`` of the bids of one chunk, in place (a chunk lies
    inside one cycle; its ids have run on already)."""
    cycle = int(batch["id"][0]) // period_events(cfg)
    if cycle:
        shift = np.where(batch["event_type"] == BID,
                         np.int64(cycle * auctions_per_period(cfg)),
                         np.int64(0))
        batch["key"] += shift
        batch["auction"] += shift
    return batch


def _one_time_a_chunk(log):
    if np.any(np.asarray(log["off_us"])) or log.get("own_ts"):
        raise ValueError("this reference is written for chunks that carry "
                         "one event time each (the closed loop)")


def _panes(cfg, seed, log):
    """``{pane: (auctions ascending, bids of each, time of the last bid)}``,
    a pane being one slide of event time; a chunk lies in one pane."""
    slide_us = int(cfg["shapes"]["slide_us"])
    chunk, period = int(log["chunk"]), period_events(cfg)
    per_cycle = auctions_per_period(cfg)
    cols = _nexmark._period(cfg, seed)
    cache, parts = {}, {}
    for j, base in enumerate(np.asarray(log["base_us"], dtype=np.int64)):
        cycle, phase = divmod(j * chunk, period)
        if phase not in cache:
            sl = slice(phase, phase + chunk)
            cache[phase] = np.unique(
                cols["auction"][sl][cols["event_type"][sl] == BID],
                return_counts=True)
        auctions, counts = cache[phase]
        if len(auctions):
            part = parts.setdefault(int(base) // slide_us, [[], [], -1])
            part[0].append(auctions + cycle * per_cycle)
            part[1].append(counts)
            part[2] = max(part[2], int(base))
    return {p: _sum_by_auction(np.concatenate(a), np.concatenate(c)) + (last,)
            for p, (a, c, last) in parts.items()}


def _sum_by_auction(auctions, counts):
    order = np.argsort(auctions, kind="stable")
    auctions, counts = auctions[order], counts[order]
    first = np.concatenate(([0], np.flatnonzero(np.diff(auctions)) + 1))
    return auctions[first], np.add.reduceat(counts, first)


def _table(wids, rows, closes=None):
    def col(i):
        return np.asarray([r[i] for r in rows], dtype=np.int64)

    out = {"key": np.zeros(len(wids), dtype=np.int64),
           "wid": np.asarray(wids, dtype=np.int64), "auction": col(0),
           "num": col(1), "bids": col(2), "lastUpdate": col(3)}
    if closes is not None:
        out["_closes_at_us"] = np.asarray(closes, dtype=np.int64)
    return out


def expected(cfg, seed, log, acc_dtype=np.int64):
    """Every window result, as columns ``key, wid, auction, num, bids,
    lastUpdate`` sorted by ``wid``, and ``_closes_at_us``: the event time of
    the first chunk at or past the window's end, which closes it whatever
    auctions it bids on (compared with nothing; it tells which results are due
    while the stream runs on).  ``acc_dtype=np.int8`` is the lower-precision
    control: each auction's count narrowed to int8 before the counts are
    compared, two widths under the int32 the device holds them in."""
    _one_time_a_chunk(log)
    shp = cfg["shapes"]
    win_us, slide_us = int(shp["win_us"]), int(shp["slide_us"])
    if win_us % slide_us:
        raise ValueError("the window is no whole number of slides")
    panes = _panes(cfg, seed, log)
    base_us = np.asarray(log["base_us"], dtype=np.int64)
    wids, rows, closes = [], [], []
    for wid in range(max(panes, default=-1) + 1):
        held = [panes[p] for p in range(wid, wid + win_us // slide_us)
                if p in panes]
        if not held:
            continue
        auctions, counts = _sum_by_auction(
            np.concatenate([h[0] for h in held]),
            np.concatenate([h[1] for h in held]))
        seen = counts.astype(acc_dtype).astype(np.int64)
        top = int(np.flatnonzero(seen == seen.max())[0])     # lowest id
        wids.append(wid)
        rows.append((int(auctions[top]), int(seen[top]), int(counts.sum()),
                     max(h[2] for h in held)))
        j = int(np.searchsorted(base_us, wid * slide_us + win_us))
        closes.append(NEVER if j >= len(base_us) else int(base_us[j]))
    return _table(wids, rows, closes)


def brute_force(cfg, seed, log):
    """The same answers by a loop over every event into a dictionary
    ``(window, auction) -> count`` (tests only)."""
    _one_time_a_chunk(log)
    shp = cfg["shapes"]
    win_us, slide_us = int(shp["win_us"]), int(shp["slide_us"])
    chunk, period = int(log["chunk"]), period_events(cfg)
    per_cycle = auctions_per_period(cfg)
    cols = _nexmark._period(cfg, seed)
    counts, total, last = {}, {}, {}
    for j, base in enumerate(log["base_us"]):
        ts = int(base)
        for e in range(chunk):
            cycle, p = divmod(j * chunk + e, period)
            if cols["event_type"][p] != BID:
                continue
            auction = int(cols["auction"][p]) + cycle * per_cycle
            wid = max((ts - win_us) // slide_us + 1, 0)
            while wid * slide_us <= ts:
                counts[(wid, auction)] = counts.get((wid, auction), 0) + 1
                total[wid] = total.get(wid, 0) + 1
                last[wid] = max(last.get(wid, -1), ts)
                wid += 1
    best = {}
    for (wid, auction), n in counts.items():
        cur = best.get(wid)
        if cur is None or n > cur[1] or (n == cur[1] and auction < cur[0]):
            best[wid] = (auction, n)
    wids = sorted(best)
    return _table(wids, [best[w] + (total[w], last[w]) for w in wids])


def events_of_missing(cfg, n_missing_windows):
    """Events whose result never arrived, for ``failed``: a missing result
    stands for at least one bid."""
    return n_missing_windows
