"""Plain reference of the ``spatial_pf`` deployment: the stream and its
answers.

numpy only; nothing of the program is imported.  The stream is
``spatial_wf_oracle``'s, letter for letter (its ``columns``, ``id_shift``,
``period_events``: ONE fixed draw of ``template_events`` points on an integer
grid, rotated by the seed, cycled; the event's time is the generator's), and
so is the question: the **skyline** (Pareto frontier under minimisation in
both coordinates; identical points leave each other alive) of the points of
every time-based sliding window ``[w * slide_us, w * slide_us + win_us)`` that
holds a point, as ``size`` and ``checksum`` (the sum of ``x + y`` over it).
A skyline is decided by comparisons alone and the checksum is a sum of whole
numbers, so the comparison's limits are 0.

What differs is the size: at this deployment's rate a window holds millions
of points and a 50 s run a hundred million, so ``expected`` neither lays the
stream out nor sorts a window whole.  It finds each window's bounds in the
generator's log by bisection, and reaches a window's skyline through the
skylines of the panes it is made of (``pane = gcd(win_us, slide_us)``; a pane
is computed once, by ``spatial_wf_oracle.skyline_mask``: sort and running
minimum), because ``skyline(A + B) = skyline(skyline(A) + skyline(B))`` -- a
point that some point of its window dominates is dominated by a point of that
point's own pane skyline.  ``brute_force`` knows neither: the event times
laid out in full, every pair of a whole window's points (tests: small
sizes); the tests tie the two.

The control (``acc_dtype=np.float16``) holds the coordinates as a float16
ring would, as ``spatial_wf_oracle``'s.

Guarantees the comparison holds the deployment to: every window's result
exact; each window once; windows in order at the sink.  ``expected`` also
notes, per window, the largest pane skyline it merged (``_pane_front_max``,
compared with nothing): the program must raise, not truncate, where that
passes its ``cap``.
"""

from __future__ import annotations

import math

import numpy as np

from . import spatial_wf_oracle as _wf
from .spatial_wf_oracle import (NEVER, columns, events_of_missing,  # noqa: F401
                                id_shift, period_events)


def pane_us(cfg):
    shp = cfg["shapes"]
    return math.gcd(int(shp["win_us"]), int(shp["slide_us"]))


def _result_ts(cfg, index, t_last):
    """A window's result carries the time of the last pane merged into it:
    the window's last microsecond, or the last microsecond of the stream's
    last pane for the windows the stream's end leaves open."""
    shp, pane = cfg["shapes"], pane_us(cfg)
    return np.minimum(index * int(shp["slide_us"]) + int(shp["win_us"]) - 1,
                      (t_last // pane + 1) * pane - 1)


class _Times:
    """Event times of the logged stream without laying them out: event
    ``j * chunk + k`` is due at ``base_us[j] + off_us[k]``, and they never
    fall."""

    def __init__(self, log):
        if log.get("own_ts"):
            raise ValueError("this stream takes the generator's event times")
        self.chunk = int(log["chunk"])
        self.base = np.asarray(log["base_us"], dtype=np.int64)
        self.off = np.asarray(log["off_us"], dtype=np.int64)[:self.chunk]
        self.n = len(self.base) * self.chunk
        self.last = self.base + (self.off[-1] if len(self.off) else 0)
        if (np.diff(self.off) < 0).any() \
                or (self.base[1:] < self.last[:-1]).any():
            raise ValueError("event times fall: the windows below assume "
                             "order")

    def at(self, i):
        """Times of the events ``i`` (an index array)."""
        i = np.asarray(i, dtype=np.int64)
        return self.base[i // self.chunk] + self.off[i % self.chunk]

    def first_at_or_after(self, t):
        """Index of the first event due at or after each time of ``t``
        (``n`` where none is)."""
        t = np.asarray(t, dtype=np.int64)
        j = np.searchsorted(self.last, t, side="left")
        inside = j < len(self.base)
        jc = np.minimum(j, max(len(self.base) - 1, 0))
        k = np.searchsorted(self.off, t - self.base[jc], side="left") \
            if len(self.base) else np.zeros_like(t)
        return np.where(inside, jc * self.chunk + k, self.n)


def _period_points(cfg, seed, acc_dtype):
    """One period of points as float64 ``x, y`` through the ring's dtype
    (``spatial_wf_oracle._coordinates``' rule: float32 holds them as they
    are, a float16 ring drops their low bits and saturates)."""
    period = period_events(cfg)
    return _wf._coordinates(cfg, seed, period, acc_dtype)


def _results(cfg, seed, log, acc_dtype):
    shp = cfg["shapes"]
    win, slide = int(shp["win_us"]), int(shp["slide_us"])
    times = _Times(log)
    if not times.n:
        z = np.zeros(0, dtype=np.int64)
        return {"key": z, "wid": z, "size": z, "checksum": z, "ts": z,
                "_closes_at_us": z, "_pane_front_max": z}
    t_last = int(times.last[-1])
    index = np.arange(t_last // slide + 1, dtype=np.int64)
    lo = times.first_at_or_after(index * slide)
    hi = times.first_at_or_after(index * slide + win)
    closes = np.where(hi < times.n, times.at(np.minimum(hi, times.n - 1)),
                      NEVER)
    held = hi > lo
    index, closes = index[held], closes[held]
    px, py = _period_points(cfg, seed, acc_dtype)
    size, checksum, front = _by_panes(cfg, times, px, py, index)
    return {"key": np.zeros(len(index), dtype=np.int64), "wid": index,
            "size": size, "checksum": checksum,
            "ts": _result_ts(cfg, index, t_last), "_closes_at_us": closes,
            "_pane_front_max": front}


def _stretch(px, py, lo, hi):
    """``x, y`` of the events ``lo .. hi-1`` of the cycled stream."""
    i = np.arange(lo, hi, dtype=np.int64) % len(px)
    return px[i], py[i]


def _by_panes(cfg, times, px, py, index):
    """Each window's skyline from its panes' skylines, a pane computed
    once."""
    shp = cfg["shapes"]
    pane, slide = pane_us(cfg), int(shp["slide_us"])
    per_win, per_slide = int(shp["win_us"]) // pane, slide // pane
    n_panes = int(index[-1]) * per_slide + per_win
    edges = times.first_at_or_after(
        np.arange(n_panes + 1, dtype=np.int64) * pane)
    fx, fy = [], []
    for a, b in zip(edges[:-1].tolist(), edges[1:].tolist()):
        x, y = _stretch(px, py, a, b)
        alive = _wf.skyline_mask(x, y)
        fx.append(x[alive])
        fy.append(y[alive])
    n_front = np.asarray([len(f) for f in fx], dtype=np.int64)
    size = np.zeros(len(index), dtype=np.int64)
    checksum = np.zeros(len(index), dtype=np.int64)
    front = np.zeros(len(index), dtype=np.int64)
    for i, w in enumerate(index.tolist()):
        p0 = w * per_slide
        x = np.concatenate(fx[p0:p0 + per_win])
        y = np.concatenate(fy[p0:p0 + per_win])
        alive = _wf.skyline_mask(x, y)
        size[i] = np.count_nonzero(alive)
        checksum[i] = int((x[alive] + y[alive]).sum())
        front[i] = n_front[p0:p0 + per_win].max()
    return size, checksum, front


def expected(cfg, seed, log, acc_dtype=np.float32):
    """Every window result of the stream the log describes, as columns
    ``key, wid, size, checksum, ts`` in window order -- ``ts`` as
    ``_result_ts`` says -- with ``_closes_at_us`` (which results are due while
    the stream runs on) and ``_pane_front_max`` (the largest pane skyline a
    window merged), both compared with nothing.  ``acc_dtype=np.float16`` is
    the lower-precision control."""
    return _results(cfg, seed, log, acc_dtype)


def brute_force(cfg, seed, log):
    """The same answers with no pane and no bisection anywhere: the event
    times laid out in full, every pair of a whole window's points tested
    (tests only: small sizes)."""
    ts = _wf._event_times(log)
    index, lo, hi, _closes = _wf._windows(cfg, ts)
    px, py = _period_points(cfg, seed, np.float32)
    size = np.zeros(len(index), dtype=np.int64)
    checksum = np.zeros(len(index), dtype=np.int64)
    for i, (a, b) in enumerate(zip(lo.tolist(), hi.tolist())):
        x, y = _stretch(px, py, a, b)
        alive = _wf.skyline_mask_all_pairs(x, y)
        size[i] = np.count_nonzero(alive)
        checksum[i] = int((x[alive] + y[alive]).sum())
    return {"key": np.zeros(len(index), dtype=np.int64), "wid": index,
            "size": size, "checksum": checksum,
            "ts": _result_ts(cfg, index, int(ts[-1]) if len(ts) else 0)}
