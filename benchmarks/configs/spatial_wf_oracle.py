"""Plain reference of the ``spatial_wf`` deployment: the stream and its
answers.

numpy only; nothing of the program is imported.  The stream is a function of
``(seed, event index)``: event ``i`` has key 0, id ``i`` and a point ``(x, y)``
from one fixed draw of ``template_events`` points, rotated by the seed and
repeating every ``template_events`` events.  Both coordinates are uniform
whole numbers in ``[0, grid_side)`` held as float32 (``grid_side`` is 65,536:
every coordinate and every sum of two is exact in float32, and in float64).
The event's time is the generator's (its due time in the open loop).

The deployment keeps the **skyline** (the Pareto frontier under minimisation
in both coordinates) of the points of every time-based sliding window
``[w * slide_us, w * slide_us + win_us)``, ``w = 0, 1, ...``, that holds a
point.  Point ``j`` dominates point ``i`` iff ``x_j <= x_i`` and
``y_j <= y_i`` and one of the two strictly; identical points do not dominate
each other, so every copy of a skyline point is on the skyline.  A window's
result is ``size``, the number of skyline points, and ``checksum``, the sum
of ``x + y`` over them.  A skyline is decided by comparisons alone, so the
answer is exact whatever the order of evaluation: the comparison's limits are
0.  ``expected`` asserts for every window that ``size * 2 * (grid_side - 1)``
stays under 2^24, so that a float32 sum of the checksum is exact too.

``expected`` uses the textbook two-dimensional method, after the point with
the lowest ``x + y`` has ruled out the bulk: sort a window's points
by ``x`` then ``y``; of each run of equal ``x`` only the points at its lowest
``y`` can be on the skyline, and they are iff that ``y`` lies strictly under
every ``y`` at a smaller ``x`` (a running minimum).  ``brute_force`` tests
every pair.  The control (``acc_dtype=np.float16``) holds the coordinates as
a float16 ring would: above 2,048 they lose their low bits, points collapse
onto each other and the sums move.

Guarantees the comparison holds the deployment to: every window's result
exact; each window once; windows in order at the sink.
"""

from __future__ import annotations

import numpy as np

_POINTS = {}
NEVER = np.iinfo(np.int64).max
#: a float32 holds every whole number below this
_F32_EXACT = 1 << 24


def period_events(cfg):
    return int(cfg["stream"]["template_events"])


def id_shift(cfg, n_events):
    """What to add to ``id`` after ``n_events`` events (one key: ids run
    on)."""
    return n_events


def _points(cfg, seed):
    """One period of points, ``(period, 2)`` float32, as the seed gives them:
    ONE draw, the same for every seed (``stream.draw_seed``), which the seed
    rotates by a whole number of rows."""
    period, side = period_events(cfg), int(cfg["shapes"]["grid_side"])
    rot = abs(int(seed)) % period
    key = (period, side, int(cfg["stream"]["draw_seed"]), rot)
    if key not in _POINTS:
        _POINTS.clear()
        rng = np.random.default_rng(key[2])
        base = rng.integers(0, side, size=(period, 2)).astype(np.float32)
        _POINTS[key] = np.roll(base, -rot, axis=0)
    return _POINTS[key]


def columns(cfg, seed, start, n):
    """Columns of events ``start .. start+n-1`` (inside one period)."""
    pts = _points(cfg, seed)[start:start + n]
    return {"key": np.zeros(n, dtype=np.int64),
            "id": np.arange(start, start + n, dtype=np.int64),
            "x": pts[:, 0], "y": pts[:, 1]}


def skyline_mask(x, y):
    """Which of the points ``(x[i], y[i])`` are on the skyline.  First the
    point with the lowest ``x + y`` rules out every point it dominates in
    both coordinates strictly (whatever such a point dominates, it dominates
    too, so the rest keeps its skyline); then one sort of what is left:
    ``O(n log n)`` at worst, a few thousand points of 100,000 on a uniform
    draw."""
    n = len(x)
    if n == 0:
        return np.zeros(0, dtype=bool)
    m = int(np.argmin(x + y))
    keep = np.flatnonzero((x <= x[m]) | (y <= y[m]))
    out = np.zeros(n, dtype=bool)
    out[keep] = _skyline_mask_sorted(x[keep], y[keep])
    return out


def _skyline_mask_sorted(x, y):
    """The textbook method on points none of which is ruled out yet."""
    n = len(x)
    order = np.lexsort((y, x))                    # by x, then y
    xs, ys = x[order], y[order]
    first = np.ones(n, dtype=bool)                # first of a run of equal x
    first[1:] = xs[1:] != xs[:-1]
    run = np.cumsum(first) - 1
    lowest = ys[first]                            # each run's lowest y
    # the lowest y at any smaller x: a running minimum, one run behind
    before = np.concatenate(([np.inf], np.minimum.accumulate(lowest)[:-1]))
    alive = (ys == lowest[run]) & (lowest < before)[run]
    out = np.empty(n, dtype=bool)
    out[order] = alive
    return out


def skyline_mask_all_pairs(x, y):
    """The same by the definition, point against every point (tests)."""
    out = np.ones(len(x), dtype=bool)
    for i in range(len(x)):
        out[i] = not np.any((x <= x[i]) & (y <= y[i])
                            & ((x < x[i]) | (y < y[i])))
    return out


def _coordinates(cfg, seed, n_events, acc_dtype):
    """``x, y`` of events ``0 .. n_events-1`` as float64, through the ring's
    dtype: float32 holds them as they are; a float16 ring (the control) drops
    their low bits, and what overflows it is held at its largest finite
    value so that a checksum stays a number."""
    period = period_events(cfg)
    pts = _points(cfg, seed)
    reps = -(-n_events // period)
    pts = np.tile(pts, (reps, 1))[:n_events] if reps > 1 else pts[:n_events]
    if np.dtype(acc_dtype) != np.float32:
        top = float(np.finfo(acc_dtype).max)
        with np.errstate(over="ignore"):
            pts = np.minimum(pts.astype(acc_dtype).astype(np.float64), top)
    return pts[:, 0].astype(np.float64), pts[:, 1].astype(np.float64)


def _event_times(log):
    chunk = int(log["chunk"])
    base_us = np.asarray(log["base_us"], dtype=np.int64)
    off_us = np.asarray(log["off_us"], dtype=np.int64)
    if log.get("own_ts"):
        raise ValueError("this stream takes the generator's event times")
    ts = (base_us[:, None] + off_us[None, :chunk]).reshape(-1)
    if len(ts) > 1 and (ts[1:] < ts[:-1]).any():
        raise ValueError("event times fall: the windows below assume order")
    return ts


def _windows(cfg, ts):
    """``(index, lo, hi, closes_at)`` of every window that holds an event:
    its events are ``lo .. hi-1``; it is closed by the first event at or past
    its end (``NEVER`` if the stream ends first)."""
    win, slide = int(cfg["shapes"]["win_us"]), int(cfg["shapes"]["slide_us"])
    if not len(ts):
        z = np.zeros(0, dtype=np.int64)
        return z, z, z, z
    index = np.arange(int(ts[-1]) // slide + 1, dtype=np.int64)
    lo = np.searchsorted(ts, index * slide, side="left")
    hi = np.searchsorted(ts, index * slide + win, side="left")
    closes = np.where(hi < len(ts), ts[np.minimum(hi, len(ts) - 1)], NEVER)
    held = hi > lo
    return index[held], lo[held], hi[held], closes[held]


def _results(cfg, seed, log, mask_of, acc_dtype=np.float32):
    shp = cfg["shapes"]
    win, slide = int(shp["win_us"]), int(shp["slide_us"])
    ts = _event_times(log)
    x, y = _coordinates(cfg, seed, len(ts), acc_dtype)
    index, lo, hi, closes = _windows(cfg, ts)
    size = np.zeros(len(index), dtype=np.int64)
    checksum = np.zeros(len(index), dtype=np.int64)
    top = 2 * (int(shp["grid_side"]) - 1)         # the largest x + y
    for i, (a, b) in enumerate(zip(lo.tolist(), hi.tolist())):
        alive = mask_of(x[a:b], y[a:b])
        size[i] = np.count_nonzero(alive)
        checksum[i] = int((x[a:b][alive] + y[a:b][alive]).sum())
    if (np.dtype(acc_dtype) == np.float32 and len(size)
            and int(size.max()) * top >= _F32_EXACT):
        raise AssertionError(
            f"a window's skyline holds {int(size.max())} points of up to "
            f"{top} each: its checksum may pass 2^24, where a float32 sum "
            f"is no longer exact")
    return {"key": np.zeros(len(index), dtype=np.int64), "wid": index,
            "size": size, "checksum": checksum,
            "ts": index * slide + win - 1, "_closes_at_us": closes}


def expected(cfg, seed, log, acc_dtype=np.float32):
    """Every window result of the stream the log describes, as columns
    ``key, wid, size, checksum, ts`` in window order -- ``ts`` the window's
    last microsecond -- and ``_closes_at_us`` (compared with nothing; it
    tells which results are due while the stream runs on).
    ``acc_dtype=np.float16`` is the lower-precision control."""
    return _results(cfg, seed, log, skyline_mask, acc_dtype)


def brute_force(cfg, seed, log):
    """The same answers with every pair of a window's points tested (tests
    only: small sizes)."""
    out = _results(cfg, seed, log, skyline_mask_all_pairs)
    del out["_closes_at_us"]
    return out


def events_of_missing(cfg, n_missing_windows):
    """Events whose result never arrived, for ``failed``: a missing result
    stands for at least one event."""
    return n_missing_windows
