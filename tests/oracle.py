"""Brute-force oracle: a literal per-tuple simulation of the reference
Win_Seq state machine (win_seq.hpp:268-474, window.hpp).  Deliberately slow
and obvious — used only to differentially validate the vectorised engine.
"""

from __future__ import annotations

import bisect
import math


class OracleWinSeq:
    def __init__(self, win_len, slide_len, win_type, func, is_nic,
                 config=None, role="SEQ", map_indexes=(0, 1)):
        # config = (id_outer, n_outer, slide_outer, id_inner, n_inner, slide_inner)
        self.win = win_len
        self.slide = slide_len
        self.wt = win_type  # "CB" | "TB"
        self.func = func    # NIC: f(key,gwid,rows)->value ; INC: f(key,gwid,row,acc)->acc
        self.is_nic = is_nic
        self.cfg = config or (0, 1, slide_len, 0, 1, slide_len)
        self.role = role
        self.map_indexes = map_indexes
        self.keys = {}

    def _kd(self, key):
        kd = self.keys.get(key)
        if kd is None:
            io, no, so, ii, ni, si = self.cfg
            first_gwid = ((ii - (key % ni) + ni) % ni) * no + (io - (key % no) + no) % no
            init_outer = ((io - (key % no) + no) % no) * so
            init_inner = ((ii - (key % ni) + ni) % ni) * si
            initial = init_inner if self.role in ("WLQ", "REDUCE") else init_outer + init_inner
            kd = {
                "archive": [],  # list of (pos, rowdict) sorted by pos
                "wins": [],     # list of window dicts, in lwid order
                "next_lwid": 0,
                "rcv": 0,
                "last_pos": None,
                "emit": self.map_indexes[0] if self.role == "MAP" else 0,
                "first_gwid": first_gwid,
                "initial": initial,
            }
            self.keys[key] = kd
        return kd

    def _emit(self, key, kd, w, rows_or_acc):
        if self.is_nic:
            value = self.func(key, w["gwid"], rows_or_acc)
        else:
            value = rows_or_acc
        rid = w["gwid"]
        if self.role == "MAP":
            rid = kd["emit"]
            kd["emit"] += self.map_indexes[1]
        elif self.role == "PLQ":
            io, no, so, ii, ni, si = self.cfg
            rid = ((ii - (key % ni) + ni) % ni) + kd["emit"] * ni
            kd["emit"] += 1
        return {"key": key, "id": rid, "ts": w["result_ts"], "value": value}

    def push(self, key, id, ts, marker=False, value=0):
        out = []
        kd = self._kd(key)
        pos = id if self.wt == "CB" else ts
        if kd["last_pos"] is not None and pos < kd["last_pos"]:
            return out
        kd["rcv"] += 1
        kd["last_pos"] = pos
        initial = kd["initial"]
        if pos < initial:
            return out
        io, no, so, ii, ni, si = self.cfg
        # last window containing pos
        if self.win >= self.slide:
            last_w = math.ceil((pos + 1 - initial) / self.slide) - 1
        else:
            n = (pos - initial) // self.slide
            last_w = n
            if (pos - initial < n * self.slide) or (pos - initial >= n * self.slide + self.win):
                if not marker:
                    return out
        row = {"key": key, "id": id, "ts": ts, "value": value}
        if not marker and self.is_nic:
            poslist = [p for p, _ in kd["archive"]]
            i = bisect.bisect_left(poslist, pos)
            kd["archive"].insert(i, (pos, row))
        # create new windows
        while kd["next_lwid"] <= last_w:
            lwid = kd["next_lwid"]
            gwid = kd["first_gwid"] + lwid * no * ni
            w = {
                "lwid": lwid, "gwid": gwid,
                "result_ts": (gwid * self.slide + self.win - 1) if self.wt == "TB" else 0,
                "acc": None if self.is_nic else self.func(key, gwid, None, None),
                "first_pos": None, "firing_pos": None,
            }
            kd["wins"].append(w)
            kd["next_lwid"] += 1
        # evaluate open windows
        fired = 0
        for w in kd["wins"]:
            if self.wt == "CB":
                is_fired = id > (self.win + w["lwid"] * self.slide - 1) + initial
            else:
                is_fired = ts >= (self.win + w["lwid"] * self.slide) + initial
            if not is_fired:
                # CONTINUE
                if w["first_pos"] is None:
                    w["first_pos"] = pos
                if self.wt == "CB":
                    w["result_ts"] = ts
                if not self.is_nic and not marker:
                    w["acc"] = self.func(key, w["gwid"], row, w["acc"])
            else:
                if w["firing_pos"] is None:
                    w["firing_pos"] = pos
                if self.is_nic:
                    if w["first_pos"] is None:
                        rows = []
                    else:
                        poslist = [p for p, _ in kd["archive"]]
                        lo = bisect.bisect_left(poslist, w["first_pos"])
                        hi = bisect.bisect_left(poslist, w["firing_pos"])
                        rows = [r for _, r in kd["archive"][lo:hi]]
                    out.append(self._emit(key, kd, w, rows))
                    if w["first_pos"] is not None:
                        poslist = [p for p, _ in kd["archive"]]
                        cut = bisect.bisect_left(poslist, w["first_pos"])
                        kd["archive"] = kd["archive"][cut:]
                else:
                    out.append(self._emit(key, kd, w, w["acc"]))
                fired += 1
        kd["wins"] = kd["wins"][fired:]
        return out

    def eos(self):
        out = []
        for key, kd in self.keys.items():
            for w in kd["wins"]:
                if self.is_nic:
                    if w["first_pos"] is None:
                        rows = []
                    else:
                        poslist = [p for p, _ in kd["archive"]]
                        lo = bisect.bisect_left(poslist, w["first_pos"])
                        rows = [r for _, r in kd["archive"][lo:]]
                    out.append(self._emit(key, kd, w, rows))
                else:
                    out.append(self._emit(key, kd, w, w["acc"]))
            kd["wins"] = []
        return out


def highest_bid_windows(rows, win_len, value="price", ts="ts", rid="id",
                        carry=("auction", "bidder")):
    """Plain reference of NEXMark Q7 over one key-less stream of bids (dicts
    or structured rows): per tumbling event-time window of ``win_len`` the
    row with the highest ``value``, ties to the lowest ``rid``, as
    ``{wid: (value, carried..., its ts, count, last ts)}``.  A loop, the twin
    of ``benchmarks/configs/q7_highest_bid_oracle.py``."""
    out = {}
    for r in rows:
        w = int(r[ts]) // win_len
        cur = out.get(w)
        if cur is None:
            cur = out[w] = {"best": None, "count": 0, "last": -1}
        b = cur["best"]
        if (b is None or int(r[value]) > int(b[value])
                or (int(r[value]) == int(b[value])
                    and int(r[rid]) < int(b[rid]))):
            cur["best"] = r
        cur["count"] += 1
        cur["last"] = max(cur["last"], int(r[ts]))
    return {w: ((int(c["best"][value]),)
                + tuple(int(c["best"][f]) for f in carry)
                + (int(c["best"][ts]), c["count"], c["last"]))
            for w, c in out.items()}


def hot_items_windows(rows, win_len, slide_len, key="auction", ts="ts"):
    """Plain reference of NEXMark Q5 "hot items" over a stream of bids
    (dicts or structured rows): per sliding event-time window ``w`` =
    ``[w*slide_len, w*slide_len + win_len)`` the ``key`` with the most bids,
    ties to the lowest key, as ``{w: (key, its bids, all bids of the window,
    the window's last bid's ts)}``.  A (key, window) pair without a bid
    counts nowhere and a window without a bid gives no entry.  A loop over
    bids into a dictionary ``(window, key) -> count``, the twin of
    ``benchmarks/configs/q5_hot_items_oracle.py``."""
    counts, total, last = {}, {}, {}
    for r in rows:
        t, k = int(r[ts]), int(r[key])
        w = max((t - win_len) // slide_len + 1, 0)
        while w * slide_len <= t:
            counts[(w, k)] = counts.get((w, k), 0) + 1
            total[w] = total.get(w, 0) + 1
            last[w] = max(last.get(w, -1), t)
            w += 1
    best = {}
    for (w, k), n in counts.items():
        cur = best.get(w)
        if cur is None or n > cur[1] or (n == cur[1] and k < cur[0]):
            best[w] = (k, n)
    return {w: best[w] + (total[w], last[w]) for w in best}


def watermark_windows(rows, win_len, slide_len, holdback, value="value",
                      ts="ts", key="key", marker="marker"):
    """Plain reference of a stream-time window stage with a hold-back
    (``fire_on="stream"``, ``holdback=``) over rows in ARRIVAL order: the
    clock is the highest ``ts`` taken in (marker rows too), the watermark
    the clock less ``holdback``, and window ``w`` = ``[w*slide_len,
    w*slide_len + win_len)``, for every integer ``w``, is closed once the
    watermark has reached its end.  A row is summed into every window of its
    key that is not closed, whatever came before it, and is late if all of
    its windows are.  A stage whose first watermark lies before time 0
    starts at the first window that watermark has not closed, else at
    window 0.  Returns
    ``({(key, w): sum}, [arrival index of every late row])``: a loop over
    rows into a dictionary."""
    sums, late = {}, []
    clock = closed = None
    for i, r in enumerate(rows):
        t = int(r[ts])
        if clock is None or t > clock:
            clock = t
            upto = (clock - holdback - win_len) // slide_len + 1
            if closed is None:
                closed = upto if clock - holdback < 0 else 0
            closed = max(closed, upto)
        if marker in r.dtype.names and r[marker]:
            continue
        first = max((t - win_len) // slide_len + 1, closed)
        last = t // slide_len
        if last < first:
            late.append(i)
            continue
        for w in range(first, last + 1):
            k = (int(r[key]), w)
            sums[k] = sums.get(k, 0) + int(r[value])
    return sums, late


def _frontier(points):
    """The skyline of ``points`` (pairs, minimisation in both coordinates) by
    the definition, point against every point: ``q`` dominates ``p`` iff it
    is no greater in both coordinates and smaller in one, so identical points
    leave each other alive.  Arrival order kept."""
    return [p for p in points
            if not any(q[0] <= p[0] and q[1] <= p[1]
                       and (q[0] < p[0] or q[1] < p[1]) for q in points)]


def skyline_windows(rows, win_len, slide_len, pos="ts", by_panes=False):
    """Plain reference of the spatial suite's query (the reference's
    ``test_spatial_wf.cpp`` / ``test_spatial_pf.cpp``) over one key-less
    stream of points (dicts or structured rows with ``x``, ``y``): per
    sliding window ``w`` = ``[w*slide_len, w*slide_len + win_len)`` of
    ``pos`` (``ts``: time-based, ``id``: count-based) that holds a point,
    the skyline of its points as ``{w: (size, sum of x + y over it)}``.

    ``by_panes=False`` tests every pair of a WHOLE window's points.
    ``by_panes=True`` is the pane form: the skyline of each tumbling pane of
    ``gcd(win_len, slide_len)`` once, a window then the skyline of its panes'
    skylines -- ``skyline(A + B) = skyline(skyline(A) + skyline(B))``, what
    Pane_Farm's PLQ and WLQ compute.  The two have to agree.  Loops, the twin
    of ``benchmarks/configs/spatial_pf_oracle.py``."""
    pts = [(int(r[pos]), float(r["x"]), float(r["y"])) for r in rows]
    if not pts:
        return {}
    last = max(p[0] for p in pts)
    pane = math.gcd(win_len, slide_len)
    fronts = {}
    if by_panes:
        for t, x, y in pts:
            fronts.setdefault(t // pane, []).append((x, y))
        fronts = {p: _frontier(v) for p, v in fronts.items()}
    out = {}
    for w in range(last // slide_len + 1):
        lo, hi = w * slide_len, w * slide_len + win_len
        if by_panes:
            held = [q for p in range(lo // pane, hi // pane)
                    for q in fronts.get(p, ())]
        else:
            held = [(x, y) for t, x, y in pts if lo <= t < hi]
        if held:
            sky = _frontier(held)
            out[w] = (len(sky), sum(x + y for x, y in sky))
    return out


def join_windows(rows, win_len, side="side", left=(0, "lk"),
                 right=(1, "rk"), left_fields=(), right_fields=(), ts="ts"):
    """Plain reference of a tumbling-window inner equi-join over one stream
    whose rows carry their side (NEXMark Q8's ``CoGroupByKey`` of persons and
    auctions in ``FixedWindows``), rows in ARRIVAL order (dicts or structured
    rows): per window ``w`` = ``[w*win_len, (w+1)*win_len)`` a dictionary of
    its LEFT rows by their join key, then a loop over its RIGHT rows; one
    result per right row whose key the dictionary holds, in the right rows'
    arrival order, as ``(key, w, the later of the two ts, the right row's
    right_fields..., the left row's left_fields...)``.  A left row that
    comes after its right rows in the window matches them all the same; one
    of the window before matches nothing.  A second left row of a key in one
    window raises ``KeyError``: the left side is unique per key and window.
    Windows are listed in order.  The twin of
    ``benchmarks/configs/q8_new_users_oracle.py``."""
    (l_val, l_key), (r_val, r_key) = left, right
    lefts, rights = {}, {}
    for r in rows:
        w = int(r[ts]) // win_len
        if r[side] == l_val:
            held = lefts.setdefault(w, {})
            if int(r[l_key]) in held:
                raise KeyError(f"window {w}: a second left row of key "
                               f"{int(r[l_key])}")
            held[int(r[l_key])] = r
        elif r[side] == r_val:
            rights.setdefault(w, []).append(r)
    out = []
    for w in sorted(rights):
        held = lefts.get(w, {})
        for r in rights[w]:
            m = held.get(int(r[r_key]))
            if m is not None:
                out.append((int(r[r_key]), w, max(int(r[ts]), int(m[ts])))
                           + tuple(int(r[f]) for f in right_fields)
                           + tuple(int(m[f]) for f in left_fields))
    return out
