"""Plain reference of the ``sum_tb_late`` deployment: the stream and its
answers.

numpy only; nothing of the program is imported.  The stream is a function of
``(seed, event index)``.  Event ``i`` has key ``i % n_keys``, id
``i // n_keys`` and a value from one fixed draw of ``template_events`` events,
rotated by the seed and repeating every ``template_events`` events.  It
carries its own event time, as an offset in microseconds from its chunk's
base (the generator's clock at the chunk's creation):

* an **on-time** event's offset is the running sum of the Pareto-distributed
  gaps of its chunk's events up to it (the reference fixture advances its
  timestamp so, ``sum_tb.hpp:133-135``), scaled so that the chunk spans
  ``chunk_span_us``: offsets lie in ``[0, chunk_span_us)`` and never fall;
* one event in ``delayed_one_in`` (picked by a hash of its place in the
  period, so never a run of neighbours) is **delayed**: its offset is minus a
  uniform whole number of microseconds in ``[1, delay_max_us]``.  On arrival
  it lies that far behind its chunk's first event (Apache Beam's NEXMark
  generator holds one event in ten back so: ``probDelayedEvent = 0.1``,
  ``occasionalDelaySec = 3``).

A delay that reaches behind the stream's first event gives a negative event
time, and that is what the event has: the time line has no origin.  Window
``w`` is ``[w * slide_us, w * slide_us + win_us)`` for EVERY integer ``w``,
and the deployment sums ``value`` per key over every window that holds an
event.  Arrival order is in no answer: ``expected`` adds each chunk's events
into (key, slide) panes by their times and sums ``win_us / slide_us`` panes a
window.  The comparison's ``wid`` counts windows from the first one an event
can lie in: ``wid = w + wid_offset(cfg)``, so it never falls below 0.

The deployment closes a window when its watermark -- the highest event time
taken in, less ``holdback_us`` -- reaches the window's end.  The hold-back
covers the stream's disorder (the delay, plus one chunk's span where a fast
closed loop creates two chunks inside one span), so every event is counted:
no event is late.  ``_closes_at_us`` is the time of the first event, in
arrival order, at or past the window's end plus the hold-back.
"""

from __future__ import annotations

import numpy as np

_PERIODS = {}
NEVER = np.iinfo(np.int64).max


def period_events(cfg):
    return int(cfg["stream"]["template_events"])


def id_shift(cfg, n_events):
    """What to add to ``id`` after ``n_events`` events (whole periods)."""
    return n_events // int(cfg["shapes"]["n_keys"])


def wid_offset(cfg):
    """Windows that can hold an event before window 0: the comparison's
    ``wid`` is the window's index plus this."""
    shp, st = cfg["shapes"], cfg["stream"]
    return -((-int(st["delay_max_us"]) - int(shp["win_us"]))
             // int(shp["slide_us"]) + 1)


def _mix64(x):
    """splitmix64's finaliser over uint64 arrays (wraps by design)."""
    with np.errstate(over="ignore"):
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
        return x ^ (x >> np.uint64(31))


def _period(cfg, seed):
    """One period as the seed gives it: values, Pareto gaps (whole numbers
    >= 1) and each event's delay in microseconds (0: on time).  ONE draw, the
    same for every seed (``stream.draw_seed``), which the seed rotates by a
    whole number of key rounds, so that every seed carries the same work."""
    st, shp = cfg["stream"], cfg["shapes"]
    period, n_keys = period_events(cfg), int(shp["n_keys"])
    rot = (abs(int(seed)) % (period // n_keys)) * n_keys
    key = (period, int(st["draw_seed"]), rot, float(st["pareto_alpha"]),
           int(st["delayed_one_in"]), int(st["delay_max_us"]))
    if key not in _PERIODS:
        _PERIODS.clear()
        lo, hi = shp["value_range"]
        rng = np.random.default_rng(key[1])
        value = rng.integers(lo, hi, size=period).astype(np.int64)
        # Pareto gaps with minimum 1 (x_m) and the stated shape, in
        # sixteenths so that whole numbers keep the law's body
        gap = np.ceil((1.0 + rng.pareto(key[3], size=period))
                      * float(st["pareto_scale"]) * 16.0).astype(np.int64)
        place = np.arange(period, dtype=np.uint64)
        h = _mix64(place + np.uint64(key[1]))
        delayed = h % np.uint64(key[4]) == 0
        delay = np.where(
            delayed,
            1 + (_mix64(h) % np.uint64(key[5])).astype(np.int64),
            0).astype(np.int64)
        _PERIODS[key] = tuple(np.roll(a, -rot) for a in (value, gap, delay))
    return _PERIODS[key]


def columns(cfg, seed, start, n):
    """Columns of events ``start .. start+n-1`` (inside one period), the
    ``n`` of them being one chunk: ``ts`` is each event's offset from the
    chunk's base."""
    n_keys = int(cfg["shapes"]["n_keys"])
    span = int(cfg["stream"]["chunk_span_us"])
    value, gap, delay = (a[start:start + n] for a in _period(cfg, seed))
    i = np.arange(start, start + n, dtype=np.int64)
    run = np.cumsum(gap) - gap[0]                 # the chunk's first: 0
    on_time = run * span // (int(run[-1]) + int(gap[0]))
    return {"key": i % n_keys, "id": i // n_keys, "value": value,
            "ts": np.where(delay > 0, -delay, on_time)}


def _chunks(cfg, seed, log):
    """``(base, key, value, ts offsets)`` of every chunk the log holds, the
    columns cached by the chunk's phase in the period."""
    if not log.get("own_ts"):
        raise ValueError("this stream carries its own event times: the log "
                         "has to say own_ts")
    chunk, period = int(log["chunk"]), period_events(cfg)
    if period % chunk:
        raise ValueError("a period is a whole number of chunks")
    cache = {}
    for j, base in enumerate(np.asarray(log["base_us"], dtype=np.int64)):
        phase = (j * chunk) % period
        if phase not in cache:
            cols = columns(cfg, seed, phase, chunk)
            cache[phase] = (cols["key"], cols["value"], cols["ts"])
        yield (int(base),) + cache[phase]


def _closes_at(cfg, seed, log, ends):
    """For each window end: the time of the first event, in arrival order,
    at or past ``end + holdback_us`` (NEVER if none is)."""
    hold = int(cfg["shapes"]["holdback_us"])
    chunks = list(_chunks(cfg, seed, log))
    if not chunks:
        return np.full(len(ends), NEVER, dtype=np.int64)
    # the highest time up to and with each chunk, and inside a chunk up to
    # and with each event
    tops = np.maximum.accumulate(
        np.asarray([b + int(ts.max()) for b, _k, _v, ts in chunks]))
    out = np.full(len(ends), NEVER, dtype=np.int64)
    running = {}
    for i, need in enumerate(np.asarray(ends, dtype=np.int64) + hold):
        j = int(np.searchsorted(tops, need, side="left"))
        if j >= len(chunks):
            continue
        base, _k, _v, ts = chunks[j]
        if id(ts) not in running:
            running[id(ts)] = np.maximum.accumulate(ts)
        top = running[id(ts)]
        e = int(np.searchsorted(top, need - base, side="left"))
        out[i] = base + int(top[e])
    return out


def expected(cfg, seed, log, acc_dtype=np.int64):
    """Every window result of the stream the log describes, as columns
    ``key, wid, value, ts`` sorted by ``(key, wid)`` -- ``wid`` the window's
    index plus ``wid_offset``, ``ts`` the window's last microsecond -- and
    ``_closes_at_us`` (compared with nothing; it tells which results are due
    while the stream runs on).  ``acc_dtype`` narrower than int64 is the
    lower-precision control: the sum wraps as a narrower accumulator
    would."""
    shp = cfg["shapes"]
    n_keys = int(shp["n_keys"])
    win, slide = int(shp["win_us"]), int(shp["slide_us"])
    if win % slide:
        raise ValueError("the window is no whole number of slides")
    per_win = win // slide
    base_us = np.asarray(log["base_us"], dtype=np.int64)
    if not len(base_us):
        return {f: np.zeros(0, np.int64)
                for f in ("key", "wid", "value", "ts", "_closes_at_us")}
    delay_max = int(cfg["stream"]["delay_max_us"])
    span = int(cfg["stream"]["chunk_span_us"])
    p_lo = (int(base_us.min()) - delay_max) // slide
    n_panes = (int(base_us.max()) + span) // slide - p_lo + 1
    sums = np.zeros(n_keys * n_panes, dtype=np.int64)
    rows = np.zeros(n_keys * n_panes, dtype=np.int64)
    for base, key, value, ts in _chunks(cfg, seed, log):
        cell = key * n_panes + ((base + ts) // slide - p_lo)
        # (a chunk's pane sums stay far below 2^53: the weights are exact)
        sums += np.bincount(cell, weights=value,
                            minlength=len(sums)).astype(np.int64)
        rows += np.bincount(cell, minlength=len(rows))
    sums = sums.reshape(n_keys, n_panes)
    rows = rows.reshape(n_keys, n_panes)
    # window w holds panes w .. w + per_win - 1: a running sum over panes,
    # padded so that the windows that start before the first pane are there
    pad = ((0, 0), (per_win, per_win - 1))
    c_sum = np.cumsum(np.pad(sums, pad), axis=1)
    c_rows = np.cumsum(np.pad(rows, pad), axis=1)
    n_wins = n_panes + per_win - 1
    w_sum = c_sum[:, per_win:per_win + n_wins] - c_sum[:, :n_wins]
    w_rows = c_rows[:, per_win:per_win + n_wins] - c_rows[:, :n_wins]
    index = p_lo - (per_win - 1) + np.arange(n_wins, dtype=np.int64)
    ends = index * slide + win
    closes = _closes_at(cfg, seed, log, ends)
    held = w_rows > 0
    k_at, w_at = np.nonzero(held)                 # by (key, window)
    value = w_sum[held]
    if np.dtype(acc_dtype) != np.int64:
        value = value.astype(acc_dtype).astype(np.int64)
    return {"key": k_at.astype(np.int64),
            "wid": index[w_at] + wid_offset(cfg),
            "value": value, "ts": ends[w_at] - 1,
            "_closes_at_us": closes[w_at]}


def brute_force(cfg, seed, log):
    """The same answers by a loop over every event into a dictionary
    ``(key, window) -> sum`` (tests only: small sizes)."""
    shp = cfg["shapes"]
    win, slide = int(shp["win_us"]), int(shp["slide_us"])
    off = wid_offset(cfg)
    sums = {}
    for base, key, value, ts in _chunks(cfg, seed, log):
        for k, v, t in zip(key.tolist(), value.tolist(),
                           (base + ts).tolist()):
            for w in range((t - win) // slide + 1, t // slide + 1):
                sums[(k, w)] = sums.get((k, w), 0) + v
    pairs = sorted(sums)
    return {"key": np.asarray([k for k, _w in pairs], dtype=np.int64),
            "wid": np.asarray([w + off for _k, w in pairs], dtype=np.int64),
            "value": np.asarray([sums[p] for p in pairs], dtype=np.int64),
            "ts": np.asarray([w * slide + win - 1 for _k, w in pairs],
                             dtype=np.int64)}


def disorder(cfg, seed, log):
    """What the stream's disorder comes to over the chunks the log holds:
    the share of events delayed, the farthest an event arrives behind the
    highest time before it, in microseconds, and the share of events that
    arrive behind an earlier event of their key (tests, and ``PERF.md``)."""
    n = late = behind = 0
    farthest = 0
    top = None
    newest = {}
    for base, key, _v, ts in _chunks(cfg, seed, log):
        t = base + ts
        run = np.maximum.accumulate(t)
        if top is not None:
            run = np.maximum(run, top)
        farthest = max(farthest, int((run - t).max()))
        top = int(run[-1])
        late += int(np.count_nonzero(ts < 0))
        n += len(t)
        for k in np.unique(key).tolist():
            tk = t[key == k]
            seen = np.maximum.accumulate(
                np.concatenate(([newest.get(k, tk[0])], tk)))[:-1]
            behind += int(np.count_nonzero(tk < seen))
            newest[k] = max(int(tk.max()), newest.get(k, int(tk[0])))
    return {"delayed_share": late / max(n, 1), "farthest_behind_us": farthest,
            "behind_key_share": behind / max(n, 1)}


def events_of_missing(cfg, n_missing_windows):
    """Events whose result never arrived, for ``failed``: a missing result
    stands for at least one event."""
    return n_missing_windows
