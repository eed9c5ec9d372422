"""Vectorised multi-key incremental cores for tumbling AND sliding windows.

``WinSeqCore`` (core/winseq.py) groups each chunk by key and runs ~20 numpy
ops per key group — exact, but at 10^5 distinct keys a chunk dissolves into
10^5 tiny-array calls (~100µs each; the reference pays the same shape of
cost per tuple, win_seq.hpp:268-474).  For windows over a **monoid
reducer** (YSB's per-campaign aggregate, the Pane_Farm PLQ stage,
Win_MapReduce's MAP/REDUCE stages, every sum_test config) the whole chunk
reduces to segment arithmetic.  Tumbling (``VecIncTumblingCore``):

* a row at relative position ``r`` belongs to exactly window ``r // L``;
* windows ``[n_fired, max_r // L)`` fire, window ``max_r // L`` stays
  pending with a partial accumulator (O(1) state per key, like INC mode);
* per-(key, window) partials are one ``ufunc.reduceat`` over the chunk
  sorted by key.

Sliding (``VecIncSlidingCore``) generalises this to ``W = ceil(L/S)``
concurrently open windows per key via accumulator *lanes* — see its
docstring.

Semantics are differentially identical to ``WinSeqCore`` in INC mode (which
for a monoid equals NIC mode): out-of-order drops against the per-key
running max (win_seq.hpp:293-305), rows below the worker's ``initial_id``
dropped (win_seq.hpp:307-314), empty skipped windows fire with the monoid
identity, EOS markers advance creation/firing and overwrite result
timestamps without being folded (window.hpp:149-154), PLQ/MAP result-id
renumbering (win_seq.hpp:396-405).  Per-key state is laid out as parallel
arrays indexed by a key->slot map instead of per-key objects, so a chunk's
bookkeeping is O(rows log rows) regardless of key cardinality.
"""

from __future__ import annotations

import ctypes
import threading

import numpy as np

from .slots import segments as _segments
from .tuples import MARKER_FIELD, Schema, progress_row
from .windows import (PatternConfig, Role, WindowSpec, WinType,
                      check_dense_positions, check_stream_fire,
                      run_stream_clock, start_stream_clock)
from .. import native
from ..ops.functions import MultiReducer, Reducer
from ..ops.monoid import NP_UFUNCS, identity as monoid_identity
from ..utils import profile

_NEG_INF = np.int64(-(2 ** 62))


def vec_core_supported(spec: WindowSpec, winfunc) -> bool:
    """The fast path handles tumbling AND sliding windows + (Multi)Reducer,
    any role.  Sliding is bounded to ceil(win/slide) <= 64 open windows per
    key (per-key pending state is a (keys, W) lane array and each row folds
    into <= W windows; beyond that the general core's per-key-group path is
    the better trade).  Hopping (slide > win) stays on the general core."""
    if isinstance(winfunc, MultiReducer):
        parts = winfunc.parts
    elif isinstance(winfunc, Reducer):
        parts = [winfunc]
    else:
        return False
    if not all(p.op == "count" or p.op in NP_UFUNCS for p in parts):
        return False
    if spec.is_tumbling:
        return True
    return (spec.slide_len < spec.win_len
            and -(-spec.win_len // spec.slide_len) <= 64)


def make_vec_core(spec: WindowSpec, winfunc, fire_on: str = "key", **kw):
    """The vectorised core for `spec` (vec_core_supported must hold):
    tumbling always vectorises; sliding defers to the first chunk's key
    cardinality (LazySlidingCore).  ``fire_on="stream"`` (time-based
    windows that close on the stage's time, quiet keys retired) is one
    core for both, :class:`VecStreamCore`: its key space is the one that
    does not stay small."""
    if fire_on == "stream":
        return VecStreamCore(spec, winfunc, **kw)
    if spec.is_tumbling:
        return VecIncTumblingCore(spec, winfunc, **kw)
    return LazySlidingCore(spec, winfunc, **kw)


class VecIncTumblingCore:
    """Drop-in for WinSeqCore (process/flush/use_incremental contract),
    ``dense_positions`` included: a window whose every position arrives
    once and in order fires with the row at its last one."""

    def __init__(self, spec: WindowSpec, winfunc, config: PatternConfig = None,
                 role: Role = Role.SEQ, map_indexes=(0, 1),
                 result_ts_slide: int = None, dense_positions: bool = False):
        assert vec_core_supported(spec, winfunc)
        check_dense_positions(dense_positions, spec)
        self.spec = spec
        #: as ``WinSeqCore``'s: the closing position moves one ahead
        self._ahead = 1 if dense_positions else 0
        self.windows_fired_complete = 0 if dense_positions else None
        self.winfunc = winfunc
        self.config = config or PatternConfig.plain(spec.slide_len)
        self.role = role
        self.map_indexes = map_indexes
        self.result_ts_slide = (result_ts_slide if result_ts_slide is not None
                                else spec.slide_len)
        self.is_nic = False
        self.result_schema = Schema(**winfunc.result_fields)
        self._result_dtype = self.result_schema.dtype()
        self.pos_field = "id" if spec.win_type is WinType.CB else "ts"
        self._L = int(spec.win_len)
        self._S = int(spec.slide_len)
        parts = winfunc.parts if isinstance(winfunc, MultiReducer) else [winfunc]
        # (out_field, in_field, ufunc-or-None(=count), dtype, identity)
        self._parts = [(p.out_field, p.field, None if p.op == "count"
                        else NP_UFUNCS[p.op], p.dtype,
                        p.dtype.type(monoid_identity(p.op, p.dtype)))
                       for p in parts]
        # --- per-key state as parallel arrays (slot-indexed) ---
        from .slots import SlotMap
        self._slotmap = SlotMap(on_register=self._init_new_keys)
        self._n = 0
        self._cap = 0
        self._key = np.zeros(0, dtype=np.int64)
        self._last_pos = np.zeros(0, dtype=np.int64)
        self._initial = np.zeros(0, dtype=np.int64)
        self._fgwid = np.zeros(0, dtype=np.int64)
        self._inner_off = np.zeros(0, dtype=np.int64)   # PLQ renumbering
        self._nfired = np.zeros(0, dtype=np.int64)      # == pending lwid
        self._seen = np.zeros(0, dtype=bool)
        self._emit_ctr = np.zeros(0, dtype=np.int64)    # MAP/PLQ renumbering
        self._marker_pos = np.zeros(0, dtype=np.int64)
        self._marker_ts = np.zeros(0, dtype=np.int64)
        self._acc_ts = np.zeros(0, dtype=np.int64)      # last folded ts, pending
        self._acc = {of: np.zeros(0, dtype=dt)
                     for of, _f, _u, dt, _i in self._parts}

    def use_incremental(self):
        return self  # inherently incremental

    # ------------------------------------------------------------- key slots

    def _grow(self, need: int):
        cap = max(self._cap * 2, need, 1024)

        def g(a, fill=0):
            b = np.full(cap, fill, dtype=a.dtype)
            b[:self._n] = a[:self._n]
            return b

        self._key = g(self._key)
        self._last_pos = g(self._last_pos, _NEG_INF)
        self._initial = g(self._initial)
        self._fgwid = g(self._fgwid)
        self._inner_off = g(self._inner_off)
        self._nfired = g(self._nfired)
        self._seen = g(self._seen, False)
        self._emit_ctr = g(self._emit_ctr)
        self._marker_pos = g(self._marker_pos, _NEG_INF)
        self._marker_ts = g(self._marker_ts)
        self._grow_acc(cap)
        self._cap = cap

    def _grow_acc(self, cap: int):
        """Grow the pending-accumulator state (1D here; the sliding core
        overrides with (cap, W) lane arrays)."""
        n = self._n
        ts = np.zeros(cap, dtype=np.int64)
        ts[:n] = self._acc_ts[:n]
        self._acc_ts = ts
        for (of, _f, _u, dt, ident) in self._parts:
            b = np.full(cap, ident, dtype=dt)
            b[:n] = self._acc[of][:n]
            self._acc[of] = b

    def _init_new_keys(self, k: np.ndarray):
        """SlotMap registration hook: per-key distribution math vectorised
        (PatternConfig.first_gwid / initial_id, basic.hpp:136,
        win_seq.hpp:307-314); new slots are self._n .. self._n+len(k)-1."""
        m = len(k)
        if self._n + m > self._cap:
            self._grow(self._n + m)
        c = self.config
        sl = slice(self._n, self._n + m)
        no, ni = c.n_outer, c.n_inner
        a = (c.id_inner - (k % ni) + ni) % ni
        b = (c.id_outer - (k % no) + no) % no
        self._key[sl] = k
        self._fgwid[sl] = a * no + b
        self._inner_off[sl] = a
        if self.role in (Role.WLQ, Role.REDUCE):
            self._initial[sl] = a * c.slide_inner
        else:
            self._initial[sl] = b * c.slide_outer + a * c.slide_inner
        if self.role is Role.MAP:
            self._emit_ctr[sl] = self.map_indexes[0]
        self._n += m

    def _slots_for(self, keys: np.ndarray) -> np.ndarray:
        return self._slotmap.lookup(keys)

    # ------------------------------------------------------------- processing

    def _ingest(self, batch: np.ndarray):
        """Shared chunk intake: slot mapping, out-of-order drop against the
        per-key running max, drop of rows below the worker's initial
        position, marker-pos/ts absorption.  Returns
        ``(s, p, sorted_rows, starts, ends, mk, any_mk)`` for the kept rows
        in slot-grouped arrival order, or None when nothing survives."""
        keys = batch["key"].astype(np.int64, copy=False)
        pos = batch[self.pos_field].astype(np.int64, copy=False)
        slots = self._slots_for(keys)
        order = np.argsort(slots, kind="stable")
        s = slots[order]
        p = pos[order]
        starts, ends = _segments(s)
        # --- out-of-order drop against the per-key running max ---
        seg_first = np.zeros(len(s), dtype=bool)
        seg_first[starts] = True
        within_bad = np.zeros(len(s), dtype=bool)
        within_bad[1:] = (np.diff(p) < 0) & ~seg_first[1:]
        head_bad = p[starts] < self._last_pos[s[starts]]
        keep_s = None
        if within_bad.any() or head_bad.any():
            # the shared segmented exclusive running max (core/slots.py):
            # the reference's per-row runmax drop (win_seq.hpp:293-305)
            # with no per-key Python even when every segment is disordered
            from .slots import segmented_excl_running_max
            excl = segmented_excl_running_max(s, p, starts,
                                              self._last_pos[s[starts]])
            keep_s = p >= excl
        # update last_pos from surviving rows (win_seq.hpp updates it before
        # the initial_id filter)
        if keep_s is None:
            self._last_pos[s[starts]] = np.maximum(
                self._last_pos[s[starts]], p[ends - 1])
        else:
            liv = np.flatnonzero(keep_s)
            if len(liv) == 0:
                return None
            ls, le = _segments(s[liv])
            self._last_pos[s[liv[ls]]] = np.maximum(
                self._last_pos[s[liv[ls]]], p[liv[le - 1]])
        # --- drop rows below the worker's initial position ---
        below = p < self._initial[s]
        if below.any():
            keep_s = ~below if keep_s is None else keep_s & ~below
        if keep_s is not None:
            sub = np.flatnonzero(keep_s)
            if len(sub) == 0:
                return None
            order = order[sub]
            s = s[sub]
            p = p[sub]
            starts, ends = _segments(s)
        sorted_rows = batch[order]
        mk = sorted_rows[MARKER_FIELD]
        # --- markers: remember the last marker's pos/ts per key ---
        any_mk = bool(mk.any())
        if any_mk:
            mi = np.flatnonzero(mk)
            msl = s[mi]
            last = np.ones(len(mi), dtype=bool)
            last[:-1] = msl[1:] != msl[:-1]
            self._marker_pos[msl[last]] = p[mi[last]]
            self._marker_ts[msl[last]] = \
                sorted_rows["ts"][mi[last]].astype(np.int64)
        return s, p, sorted_rows, starts, ends, mk, any_mk

    def process(self, batch: np.ndarray) -> np.ndarray:
        if len(batch) == 0:
            return np.zeros(0, dtype=self._result_dtype)
        ing = self._ingest(batch)
        if ing is None:
            return np.zeros(0, dtype=self._result_dtype)
        s, p, sorted_rows, starts, ends, mk, any_mk = ing
        rel = p - self._initial[s]
        w = rel // self._L
        # --- per-(slot, window) fold segments over real (non-marker) rows ---
        if any_mk:
            ri = np.flatnonzero(~mk)
            r_s, r_w, r_rows = s[ri], w[ri], sorted_rows[ri]
        else:
            r_s, r_w, r_rows = s, w, sorted_rows
        if len(r_s):
            bnd = np.concatenate(([0], np.flatnonzero(
                (np.diff(r_s) != 0) | (np.diff(r_w) != 0)) + 1))
            bnd_end = np.concatenate((bnd[1:], [len(r_s)]))
            seg_slot = r_s[bnd]
            seg_w = r_w[bnd]
            seg_len = bnd_end - bnd
            seg_ts = r_rows["ts"][bnd_end - 1].astype(np.int64)
            seg_vals = {}
            for (of, field, ufunc, dt, _ident) in self._parts:
                if ufunc is None:
                    seg_vals[of] = seg_len.astype(dt)
                else:
                    seg_vals[of] = ufunc.reduceat(
                        r_rows[field].astype(dt, copy=False), bnd)
        else:
            seg_slot = seg_w = np.zeros(0, dtype=np.int64)
            seg_ts = np.zeros(0, dtype=np.int64)
            seg_vals = {of: np.zeros(0, dtype=dt)
                        for (of, _f, _u, dt, _i) in self._parts}
        # --- firing: windows [n_fired, w_max) fire; w_max stays pending ---
        u = s[starts]                       # unique slots, ascending
        fired_lo = self._nfired[u]
        if self._ahead:
            # fired_through(max_rel): the pending window is open only if a
            # row lies in it
            w_max = (rel[ends - 1] + 1) // self._L
            self._seen[u] = w_max == w[ends - 1]
            self._count_complete(w_max, fired_lo, w[starts])
        else:
            w_max = w[ends - 1]             # fired_before(max_rel), tumbling
            self._seen[u] = True
        m = w_max - fired_lo                # >= 0: kept rows are in-order
        total = int(m.sum())
        offs = np.concatenate(([0], np.cumsum(m)))
        out_slot = np.repeat(u, m)
        ar = np.arange(total, dtype=np.int64) - np.repeat(offs[:-1], m)
        out_lwid = np.repeat(fired_lo, m) + ar
        out_vals = {of: np.full(total, ident, dtype=dt)
                    for (of, _f, _u, dt, ident) in self._parts}
        out_ts = np.zeros(total, dtype=np.int64)
        # the old pending accumulator lands in each slot's first fired window
        moved = m > 0
        if moved.any():
            pp = offs[:-1][moved]
            mu = u[moved]
            for (of, _f, ufunc, dt, ident) in self._parts:
                accv = self._acc[of][mu]
                if ufunc is None:           # count: partials add
                    out_vals[of][pp] = out_vals[of][pp] + accv
                else:
                    out_vals[of][pp] = ufunc(out_vals[of][pp], accv)
                self._acc[of][mu] = ident
            out_ts[pp] = self._acc_ts[mu]
            self._acc_ts[mu] = 0
        # fold chunk segments into fired outputs / the pending accumulator
        if len(seg_slot):
            spos = np.searchsorted(u, seg_slot)
            fired_seg = seg_w < w_max[spos]
            if fired_seg.any():
                fs = np.flatnonzero(fired_seg)
                op = offs[:-1][spos[fs]] + (seg_w[fs] - fired_lo[spos[fs]])
                for (of, _f, ufunc, dt, _ident) in self._parts:
                    sv = seg_vals[of][fs]
                    if ufunc is None:
                        out_vals[of][op] = out_vals[of][op] + sv
                    else:
                        out_vals[of][op] = ufunc(out_vals[of][op], sv)
                out_ts[op] = seg_ts[fs]
            pend = ~fired_seg
            if pend.any():
                ps = np.flatnonzero(pend)
                psl = seg_slot[ps]
                for (of, _f, ufunc, dt, _ident) in self._parts:
                    sv = seg_vals[of][ps]
                    if ufunc is None:
                        self._acc[of][psl] = self._acc[of][psl] + sv
                    else:
                        self._acc[of][psl] = ufunc(self._acc[of][psl], sv)
                self._acc_ts[psl] = seg_ts[ps]
        self._nfired[u] = w_max
        if total == 0:
            return np.zeros(0, dtype=self._result_dtype)
        return self._make_results(out_slot, out_lwid, out_ts, out_vals)

    def _count_complete(self, fired_hi, fired_lo, first_done):
        """``windows_fired_complete``: of each slot's windows ``[fired_lo,
        fired_hi)`` those from ``first_done`` on, the first whose last
        position lies at or behind the chunk's first row of that slot."""
        self.windows_fired_complete += int(np.maximum(
            fired_hi - np.maximum(fired_lo, first_done), 0).sum())

    # ------------------------------------------------------------------- emit

    def _make_results(self, out_slot, out_lwid, out_ts, vals) -> np.ndarray:
        """Assemble a result batch: gwids, role renumbering
        (win_seq.hpp:396-405), CB marker ts overwrite (window.hpp:149-154),
        TB closed-form ts.  ``out_slot`` must be grouped (all of a slot's
        windows contiguous, lwids ascending)."""
        gwids = self._fgwid[out_slot] + out_lwid * self.config.gwid_stride()
        if self.spec.win_type is WinType.TB:
            ts = gwids * self.result_ts_slide + self.spec.win_len - 1
        else:
            ends_abs = (out_lwid * self._S + self._L
                        + self._initial[out_slot])
            mpos = self._marker_pos[out_slot]
            ts = np.where((mpos > _NEG_INF) & (mpos < ends_abs),
                          self._marker_ts[out_slot], out_ts)
        if self.role in (Role.MAP, Role.PLQ):
            first = np.ones(len(out_slot), dtype=bool)
            first[1:] = out_slot[1:] != out_slot[:-1]
            fidx = np.flatnonzero(first)
            cnt = np.diff(np.concatenate((fidx, [len(out_slot)])))
            rank = out_lwid - np.repeat(out_lwid[fidx], cnt)
            if self.role is Role.MAP:
                n = self.map_indexes[1]
                ids = self._emit_ctr[out_slot] + rank * n
                self._emit_ctr[out_slot[fidx]] += cnt * n
            else:
                ni = self.config.n_inner
                ids = (self._inner_off[out_slot]
                       + (self._emit_ctr[out_slot] + rank) * ni)
                self._emit_ctr[out_slot[fidx]] += cnt
        else:
            ids = gwids
        out = np.zeros(len(out_slot), dtype=self._result_dtype)
        out["key"] = self._key[out_slot]
        out["id"] = ids
        out["ts"] = ts
        for name in self.winfunc.result_fields:
            out[name] = vals[name]
        return out

    # -------------------------------------------------- keyed state migration
    # The control plane's live rescale (docs/CONTROL.md) moves per-key
    # state between sibling farm workers at an epoch barrier.  Slots are
    # never removed from the SlotMap: export NEUTRALIZES the source
    # slot (last_pos back to -inf marks it dead — a registered key
    # always has last_pos set by its first chunk), and import overwrites
    # whatever the destination slot holds.  Derived per-key fields
    # (initial, fgwid, inner_off) are recomputed by slot registration —
    # sibling workers share one PatternConfig, so they are identical.

    _FRAG_KIND = "vec_tumbling"
    #: all per-key state is in the host slot arrays — migratable
    keyed_migratable = True

    def keyed_state_keys(self) -> np.ndarray:
        live = self._last_pos[:self._n] > _NEG_INF
        return self._key[:self._n][live].copy()

    def _export_acc(self, slots) -> dict:
        out = {"acc_ts": self._acc_ts[slots].copy(),
               "acc": {of: self._acc[of][slots].copy()
                       for (of, _f, _u, _dt, _i) in self._parts}}
        self._acc_ts[slots] = 0
        for (of, _f, _u, _dt, ident) in self._parts:
            self._acc[of][slots] = ident
        return out

    def _import_acc(self, slots, frag):
        self._acc_ts[slots] = frag["acc_ts"]
        for of, v in frag["acc"].items():
            self._acc[of][slots] = v

    def keyed_state_export(self, keys: np.ndarray) -> dict:
        keys = np.ascontiguousarray(keys, dtype=np.int64)
        slots = self._slots_for(keys)
        frag = {
            "kind": self._FRAG_KIND,
            "keys": keys,
            "last_pos": self._last_pos[slots].copy(),
            "nfired": self._nfired[slots].copy(),
            "seen": self._seen[slots].copy(),
            "emit_ctr": self._emit_ctr[slots].copy(),
            "marker_pos": self._marker_pos[slots].copy(),
            "marker_ts": self._marker_ts[slots].copy(),
        }
        frag.update(self._export_acc(slots))
        self._last_pos[slots] = _NEG_INF
        self._nfired[slots] = 0
        self._seen[slots] = False
        self._emit_ctr[slots] = (self.map_indexes[0]
                                 if self.role is Role.MAP else 0)
        self._marker_pos[slots] = _NEG_INF
        self._marker_ts[slots] = 0
        return frag

    def keyed_state_import(self, frag: dict):
        if frag["kind"] != self._FRAG_KIND:
            raise TypeError(f"cannot import {frag['kind']!r} state into "
                            f"{type(self).__name__}")
        slots = self._slots_for(frag["keys"])
        self._last_pos[slots] = frag["last_pos"]
        self._nfired[slots] = frag["nfired"]
        self._seen[slots] = frag["seen"]
        self._emit_ctr[slots] = frag["emit_ctr"]
        self._marker_pos[slots] = frag["marker_pos"]
        self._marker_ts[slots] = frag["marker_ts"]
        self._import_acc(slots, frag)

    # -------------------------------------------------------------------- EOS

    def flush(self) -> np.ndarray:
        """Emit the pending window of every key that saw rows
        (win_seq.hpp:433-474); tumbling INC mode has exactly one open
        window per key."""
        slots = np.flatnonzero(self._seen[:self._n])
        if len(slots) == 0:
            return np.zeros(0, dtype=self._result_dtype)
        out_lwid = self._nfired[slots].copy()
        out_ts = self._acc_ts[slots].copy()
        vals = {of: self._acc[of][slots].copy()
                for (of, _f, _u, _dt, _i) in self._parts}
        out = self._make_results(slots, out_lwid, out_ts, vals)
        self._nfired[slots] += 1
        self._seen[slots] = False
        for (of, _f, _u, dt, ident) in self._parts:
            self._acc[of][slots] = ident
        self._acc_ts[slots] = 0
        return out


class VecIncSlidingCore(VecIncTumblingCore):
    """Vectorised multi-key incremental core for SLIDING windows
    (slide < win): the tumbling core's segment arithmetic generalised to
    ``W = ceil(win/slide)`` concurrently open windows per key.

    A row at relative position ``r`` belongs to windows
    ``[max(0, (r-L)//S + 1), r//S]`` (win_seq.hpp:324's last-window formula
    inverted); window ``w`` fires when a row with ``rel >= w*S + L``
    arrives.  Per-key pending state is a ring of W accumulator *lanes*
    (lane = w % W) in slot-indexed 2D parallel arrays: at any moment the
    windows holding data are exactly ``[n_fired, n_fired + W)``, so lanes
    never collide.  Each chunk expands rows into their (slot, window)
    memberships (<= W per row), sorts once, and folds one ``reduceat`` per
    stat — O(W * rows log rows) at any key cardinality, replacing the
    general core's per-key-group collapse.
    """

    def __init__(self, spec: WindowSpec, winfunc, config: PatternConfig = None,
                 role: Role = Role.SEQ, map_indexes=(0, 1),
                 result_ts_slide: int = None, dense_positions: bool = False):
        assert spec.slide_len < spec.win_len, "sliding only (see tumbling)"
        super().__init__(spec, winfunc, config=config, role=role,
                         map_indexes=map_indexes,
                         result_ts_slide=result_ts_slide,
                         dense_positions=dense_positions)
        self._W = -(-self._L // self._S)
        # reshape the pending state to (cap, W) lanes + created-window count
        self._ncreated = np.zeros(self._cap, dtype=np.int64)
        self._acc_ts = np.zeros((self._cap, self._W), dtype=np.int64)
        self._acc = {of: np.full((self._cap, self._W), ident, dtype=dt)
                     for (of, _f, _u, dt, ident) in self._parts}

    def _grow_acc(self, cap: int):
        n, W = self._n, self._W
        nc = np.zeros(cap, dtype=np.int64)
        nc[:n] = self._ncreated[:n]
        self._ncreated = nc
        ts = np.zeros((cap, W), dtype=np.int64)
        ts[:n] = self._acc_ts[:n]
        self._acc_ts = ts
        for (of, _f, _u, dt, ident) in self._parts:
            b = np.full((cap, W), ident, dtype=dt)
            b[:n] = self._acc[of][:n]
            self._acc[of] = b

    def process(self, batch: np.ndarray) -> np.ndarray:
        if len(batch) == 0:
            return np.zeros(0, dtype=self._result_dtype)
        ing = self._ingest(batch)
        if ing is None:
            return np.zeros(0, dtype=self._result_dtype)
        s, p, sorted_rows, starts, ends, mk, any_mk = ing
        L, S, W = self._L, self._S, self._W
        rel = p - self._initial[s]
        if any_mk:
            ri = np.flatnonzero(~mk)
            r_s, r_rel, r_rows = s[ri], rel[ri], sorted_rows[ri]
        else:
            r_s, r_rel, r_rows = s, rel, sorted_rows
        # --- expand real rows into their (slot, window) memberships ---
        hi = r_rel // S
        lo = np.maximum((r_rel - L) // S + 1, 0)
        c = hi - lo + 1                      # >= 1: sliding covers every rel
        tot = int(c.sum())
        coffs = np.concatenate(([0], np.cumsum(c)))
        e_row = np.repeat(np.arange(len(r_s), dtype=np.int64), c)
        e_w = (np.repeat(lo, c)
               + np.arange(tot, dtype=np.int64) - np.repeat(coffs[:-1], c))
        e_s = r_s[e_row]
        # one stable sort groups (slot, window) pairs, preserving arrival
        # order within each (slot stays grouped; windows interleave by row)
        span = int(e_w.max()) + 2 if tot else 1
        sidx = np.argsort(e_s * span + e_w, kind="stable")
        g_s, g_w, g_row = e_s[sidx], e_w[sidx], e_row[sidx]
        if tot:
            bnd = np.concatenate(([0], np.flatnonzero(
                (np.diff(g_s) != 0) | (np.diff(g_w) != 0)) + 1))
            bnd_end = np.concatenate((bnd[1:], [tot]))
            seg_slot = g_s[bnd]
            seg_w = g_w[bnd]
            seg_len = bnd_end - bnd
            seg_ts = r_rows["ts"][g_row[bnd_end - 1]].astype(np.int64)
            seg_vals = {}
            for (of, field, ufunc, dt, _ident) in self._parts:
                if ufunc is None:
                    seg_vals[of] = seg_len.astype(dt)
                else:
                    seg_vals[of] = ufunc.reduceat(
                        r_rows[field].astype(dt, copy=False)[g_row], bnd)
        else:
            seg_slot = seg_w = np.zeros(0, dtype=np.int64)
            seg_ts = np.zeros(0, dtype=np.int64)
            seg_vals = {of: np.zeros(0, dtype=dt)
                        for (of, _f, _u, dt, _i) in self._parts}
        # --- firing: windows [n_fired, new_fired) fire, in window order ---
        u = s[starts]                        # unique slots, ascending
        max_rel = rel[ends - 1]              # kept rows are in-order per key
        new_fired = np.maximum(
            self._nfired[u],
            np.maximum((max_rel + self._ahead - L) // S + 1, 0))
        self._ncreated[u] = np.maximum(self._ncreated[u], max_rel // S + 1)
        fired_lo = self._nfired[u]
        if self._ahead:
            self._count_complete(new_fired, fired_lo,
                                 (rel[starts] - L + S) // S)
        m = new_fired - fired_lo
        self._seen[u] = True
        total = int(m.sum())
        offs = np.concatenate(([0], np.cumsum(m)))
        out_slot = np.repeat(u, m)
        ar = np.arange(total, dtype=np.int64) - np.repeat(offs[:-1], m)
        out_lwid = np.repeat(fired_lo, m) + ar
        out_vals = {of: np.full(total, ident, dtype=dt)
                    for (of, _f, _u, dt, ident) in self._parts}
        out_ts = np.zeros(total, dtype=np.int64)
        # pending lanes land in their windows: only the first W fired per
        # slot can hold lane state (open windows live in [n_fired,
        # n_fired+W) — a row touching n_fired+W would have fired n_fired)
        take = ar < W
        if take.any():
            tsl = out_slot[take]
            tln = out_lwid[take] % W
            for (of, _f, _u, dt, ident) in self._parts:
                out_vals[of][take] = self._acc[of][tsl, tln]
                self._acc[of][tsl, tln] = ident
            out_ts[take] = self._acc_ts[tsl, tln]
            self._acc_ts[tsl, tln] = 0
        # fold chunk segments into fired outputs / the pending lanes
        if len(seg_slot):
            spos = np.searchsorted(u, seg_slot)
            fired_seg = seg_w < new_fired[spos]
            if fired_seg.any():
                fs = np.flatnonzero(fired_seg)
                op = offs[:-1][spos[fs]] + (seg_w[fs] - fired_lo[spos[fs]])
                for (of, _f, ufunc, dt, _ident) in self._parts:
                    sv = seg_vals[of][fs]
                    if ufunc is None:
                        out_vals[of][op] = out_vals[of][op] + sv
                    else:
                        out_vals[of][op] = ufunc(out_vals[of][op], sv)
                out_ts[op] = seg_ts[fs]
            pend = ~fired_seg
            if pend.any():
                ps = np.flatnonzero(pend)
                psl = seg_slot[ps]
                pln = seg_w[ps] % W          # distinct pending w => distinct
                for (of, _f, ufunc, dt, _ident) in self._parts:  # lanes
                    sv = seg_vals[of][ps]
                    if ufunc is None:
                        self._acc[of][psl, pln] = self._acc[of][psl, pln] + sv
                    else:
                        self._acc[of][psl, pln] = ufunc(
                            self._acc[of][psl, pln], sv)
                self._acc_ts[psl, pln] = seg_ts[ps]
        self._nfired[u] = new_fired
        if total == 0:
            return np.zeros(0, dtype=self._result_dtype)
        return self._make_results(out_slot, out_lwid, out_ts, out_vals)

    # keyed migration: the tumbling fragment plus the created-window
    # count; the 1D acc copies generalise to (m, W) lane rows untouched
    _FRAG_KIND = "vec_sliding"

    def keyed_state_export(self, keys: np.ndarray) -> dict:
        frag = super().keyed_state_export(keys)
        slots = self._slots_for(frag["keys"])
        frag["ncreated"] = self._ncreated[slots].copy()
        self._ncreated[slots] = 0
        return frag

    def keyed_state_import(self, frag: dict):
        super().keyed_state_import(frag)
        self._ncreated[self._slots_for(frag["keys"])] = frag["ncreated"]

    def flush(self) -> np.ndarray:
        """EOS: every created-but-unfired window fires, oldest first
        (win_seq.hpp:433-474) — at most W per key, all lane-resident."""
        W = self._W
        slots = np.flatnonzero(self._seen[:self._n])
        if len(slots) == 0:
            return np.zeros(0, dtype=self._result_dtype)
        fired_lo = self._nfired[slots]
        m = self._ncreated[slots] - fired_lo
        keep = m > 0
        slots, fired_lo, m = slots[keep], fired_lo[keep], m[keep]
        total = int(m.sum())
        if total == 0:
            self._seen[:self._n] = False
            return np.zeros(0, dtype=self._result_dtype)
        offs = np.concatenate(([0], np.cumsum(m)))
        out_slot = np.repeat(slots, m)
        ar = np.arange(total, dtype=np.int64) - np.repeat(offs[:-1], m)
        out_lwid = np.repeat(fired_lo, m) + ar
        lanes = out_lwid % W
        vals = {}
        for (of, _f, _u, dt, ident) in self._parts:
            vals[of] = self._acc[of][out_slot, lanes].copy()
            self._acc[of][out_slot, lanes] = ident
        out_ts = self._acc_ts[out_slot, lanes].copy()
        self._acc_ts[out_slot, lanes] = 0
        out = self._make_results(out_slot, out_lwid, out_ts, vals)
        self._nfired[slots] = self._ncreated[slots]
        self._seen[:self._n] = False
        return out


class VecStreamCore:
    """Time-based tumbling or sliding windows over a monoid reducer that
    close on the STAGE's time — the highest ``ts`` taken in on any key —
    and forget a key once its last open window has fired.

    The per-key cores above fire a key's window when that key's next row
    arrives (win_seq.hpp's triggerer); on a stream whose keys go quiet that
    row never comes.  Here window ``w`` is ``[w*S, w*S + L)`` for every key
    (``check_stream_fire``), so the stage keeps ONE count of fired windows:
    a row at or past the end of window ``w`` fires ``w`` for every key that
    holds rows in it, before the row itself is folded.  With a ``holdback``
    the windows close on the watermark, the clock less the hold-back.  A row
    is folded into every window of its key that has not fired, whatever
    rows came before it; a row that arrives for a window already fired is
    not folded into it, and one that finds all its windows fired is late:
    dropped and counted (``late_rows``).  So on a stream whose disorder the
    hold-back covers the results are those of the same rows in order.  An
    (key, window) pair without a row gives no result.

    State: ``W = ceil((L + holdback)/S)`` accumulator lanes a live key (lane
    ``w % W``; the open windows are ``[fired, fired + W)``, so lanes never
    collide) and the rows each lane holds.  Off a window boundary a chunk costs its fold;
    at one, O(live keys): the fire, then the retiring of every key whose
    lanes are all empty — its slot is compacted away (``SlotMap.retain``),
    and a key seen again starts as a new key.  So the state is bounded by the
    keys of the open windows.  After a fire the core sends a
    :func:`progress_row`.  Marker rows in the input move the clock and fold
    nothing.

    The fold has two forms of one algorithm, chosen from what the core can
    observe at its first chunk (:meth:`_native_plan`): where the native
    library is loaded and every part is a count or an int64 sum / min / max
    over an integer field, one C++ call a stretch between two window
    boundaries (``wf_sfold``: a hash index of key -> slot, each row straight
    into its lanes, no interpreter lock held; ``fold_native_batches`` counts
    the chunks it took); anywhere else :meth:`_fold`, numpy over the rows
    sorted by key and ``SlotMap``'s sorted view.  Both number a stretch's
    unseen keys in ascending key order, so the slots, and with them the
    order of a fire's rows, are the same.  All state is numpy arrays the
    core owns, the hash index (``_tab``) too: a deep copy is a snapshot (it
    leaves the index out; the copy rebuilds it from the keys).
    """

    fire_on = "stream"

    def __init__(self, spec: WindowSpec, winfunc, config: PatternConfig = None,
                 role: Role = Role.SEQ, map_indexes=(0, 1),
                 result_ts_slide: int = None, holdback: int = 0):
        assert vec_core_supported(spec, winfunc)
        check_stream_fire(spec, config, role, holdback)
        self.spec = spec
        #: the watermark is the clock less this (``run_stream_clock``)
        self.holdback = int(holdback)
        self._clock_started = False
        self.winfunc = winfunc
        self.is_nic = False
        self.result_schema = Schema(**winfunc.result_fields)
        self._result_dtype = self.result_schema.dtype()
        self._L = int(spec.win_len)
        self._S = int(spec.slide_len)
        # the windows open at once: a row's last window lies under the
        # clock, the first open one over the watermark
        self._W = -(-(self._L + self.holdback) // self._S)
        self._ts_slide = int(result_ts_slide if result_ts_slide is not None
                             else spec.slide_len)
        parts = winfunc.parts if isinstance(winfunc, MultiReducer) else [winfunc]
        self._parts = [(p.out_field, p.field, None if p.op == "count"
                        else NP_UFUNCS[p.op], p.dtype,
                        p.dtype.type(monoid_identity(p.op, p.dtype)))
                       for p in parts]
        from .slots import SlotMap
        self._slotmap = SlotMap(on_register=self._grow_for)
        self._cap = 0
        self._rows = np.zeros((0, self._W), dtype=np.int64)   # rows a lane
        self._acc = {of: np.zeros((0, self._W), dtype=dt)
                     for of, _f, _u, dt, _i in self._parts}
        #: the windows the stage's clock has fired, and the end of the next
        self._fired = 0
        self._next_end = self._L
        #: what the node reports (docs/OBSERVABILITY.md)
        self.keys_live_peak = 0
        self.keys_retired = 0
        self.stream_fires = 0
        self.stream_fire_rows = 0
        self.late_rows = 0
        #: the chunks the native fold took (÷ the node's batches: its share)
        self.fold_native_batches = 0
        #: None until the first chunk decides; then whether the key index is
        #: the hash table (``_tab``: cells of {key, slot}) or the SlotMap's
        self._native = None
        self._plan = (None, None)                # a chunk dtype, its plan
        self._tab = np.zeros((0, 2), dtype=np.int64)

    def __getstate__(self):
        """A copy carries no hash index: the next chunk rebuilds it from
        the keys (:meth:`_reserve`)."""
        return {**self.__dict__, "_tab": self._tab[:0]}

    @property
    def keys_live(self) -> int:
        return self._slotmap.n

    def use_incremental(self):
        return self  # inherently incremental

    # ------------------------------------------------------------- key slots

    def _grow_for(self, new_keys: np.ndarray):
        """SlotMap registration hook: room for the new slots."""
        self._grow_to(self._slotmap.n)
        self.keys_live_peak = max(self.keys_live_peak, self._slotmap.n)

    def _grow_to(self, need: int):
        """Lanes for ``need`` slots (a new slot's are empty: a retired
        slot's were reset when it went)."""
        if need > self._cap:
            cap = max(self._cap * 2, need, 1024)
            rows = np.zeros((cap, self._W), dtype=np.int64)
            rows[:self._cap] = self._rows
            self._rows = rows
            for (of, _f, _u, dt, ident) in self._parts:
                b = np.full((cap, self._W), ident, dtype=dt)
                b[:self._cap] = self._acc[of]
                self._acc[of] = b
            self._cap = cap

    def _reserve(self, need: int):
        """Room for ``need`` slots before a native fold writes: lanes, the
        slot -> key column, and an index under half full (rebuilt from the
        keys when it has to grow)."""
        self._grow_to(need)
        sm = self._slotmap
        if len(sm.keys) < self._cap:
            grown = np.empty(self._cap, dtype=np.int64)
            grown[:sm.n] = sm.keys[:sm.n]
            sm.keys = grown
        if 2 * need > len(self._tab):
            self._reindex(need)

    def _reindex(self, need: int = 0):
        """The hash index anew from the live slots' keys (O(live keys)),
        with room for ``need`` slots at least."""
        need = max(need, self._slotmap.n, 1)
        if 2 * need > len(self._tab):
            self._tab = np.empty((1 << (2 * need - 1).bit_length(), 2),
                                 dtype=np.int64)
        native.enabled().wf_sfold_index(
            self._tab.ctypes.data, len(self._tab),
            self._slotmap.keys.ctypes.data, self._slotmap.n)

    # ------------------------------------------------------------- processing

    def process(self, batch: np.ndarray) -> np.ndarray:
        if not len(batch):
            return np.zeros(0, dtype=self._result_dtype)
        plan = self._native_plan(batch.dtype)
        if self._native is None:
            self._native = plan is not None
        elif self._native and plan is None:     # the stream changed its record
            self._slotmap.reindex()
            self._native = False
        outs = (self._process_native(batch, plan) if self._native
                else run_stream_clock(self, batch, self._fold))
        if not outs:
            return np.zeros(0, dtype=self._result_dtype)
        return outs[0] if len(outs) == 1 else np.concatenate(outs)

    #: a part's ufunc (None: a count) as ``wf_sfold`` numbers the operations
    _NATIVE_OPS = {None: 0, np.add: 1, np.minimum: 2, np.maximum: 3}

    def _native_plan(self, dtype):
        """What ``wf_sfold`` needs to read a chunk of this ``dtype`` --
        the offsets of key, ts and marker and one {op, offset, kind} a part
        -- or None where it cannot run: no native library, a part that is
        not a count or an int64 sum / min / max over an integer field."""
        if self._plan[0] is dtype or self._plan[0] == dtype:
            return self._plan[1]
        lib = native.enabled()
        desc = []
        for (_of, name, ufunc, acc_dtype, _ident) in self._parts:
            op = self._NATIVE_OPS.get(ufunc)
            if op == 0:
                desc += [0, 0, 0]
                continue
            field, offset = dtype.fields[name][:2]
            if (op is None or acc_dtype != np.int64
                    or field.kind not in "iu" or field.shape):
                break
            desc += [op, offset,
                     field.itemsize * (-1 if field.kind == "u" else 1)]
        plan = None
        if (lib is not None and len(desc) == 3 * len(self._parts)
                and len(self._parts) <= lib.wf_sfold_max_parts()
                and all(dtype.fields[f][0] == np.int64 for f in ("key", "ts"))
                and dtype.fields[MARKER_FIELD][0].itemsize == 1):
            plan = ([dtype.fields[f][1] for f in ("key", "ts", MARKER_FIELD)],
                    np.array(desc, dtype=np.int64))
        self._plan = (dtype, plan)
        return plan

    def _process_native(self, batch: np.ndarray, plan) -> list:
        """One chunk through ``wf_sfold``: what :func:`run_stream_clock`
        does with :meth:`_fold`, the stretch up to the next boundary a call
        (it finds the boundary, skips the markers, counts the late rows)."""
        offs, desc = plan
        lib, sm = native.enabled(), self._slotmap
        start_stream_clock(self, int(batch["ts"][0]))
        self.fold_native_batches += 1
        n, lo, outs = len(batch), 0, []
        io = np.zeros(4, dtype=np.int64)
        while lo < n:
            # a stretch: one call, or more where the keys outgrow their room
            io[0] = io[2] = sm.n
            io[3] = 1
            geom = np.array([self._L, self._S, self._W, self._fired],
                            dtype=np.int64)
            while io[3]:
                self._reserve(sm.n + 1)
                acc = (ctypes.c_void_p * len(self._parts))(*(
                    self._acc[of].ctypes.data for of, *_ in self._parts))
                lo = lib.wf_sfold(
                    batch.ctypes.data, batch.strides[0], lo, n, *offs,
                    self._next_end + self.holdback, geom.ctypes.data,
                    self._tab.ctypes.data, len(self._tab),
                    sm.keys.ctypes.data, self._cap, self._rows.ctypes.data,
                    len(self._parts), desc.ctypes.data, acc, io.ctypes.data)
                sm.n = int(io[0])
                if io[1]:
                    self.late_rows += int(io[1])
                    profile.add("late_rows", int(io[1]))
            self.keys_live_peak = max(self.keys_live_peak, sm.n)
            if lo < n:
                outs.extend(self._fire(int(batch["ts"][lo]) - self.holdback))
        return outs

    def _fold(self, rows: np.ndarray, ts: np.ndarray):
        """Fold rows into the lanes of the open windows they lie in:
        one sort by key groups the rows, one ``reduceat`` a statistic folds
        each group, and a group's partial goes to the lane of every window
        its rows lie in."""
        L, S, W = self._L, self._S, self._W
        hi = ts // S
        # a window under `fired` has gone: the row is late for it
        lo = np.maximum((ts - L) // S + 1, self._fired)
        if (hi < lo).any():                 # late for every window of theirs
            live = np.flatnonzero(hi >= lo)
            self.late_rows += len(rows) - len(live)
            profile.add("late_rows", len(rows) - len(live))
            rows, hi, lo = rows[live], hi[live], lo[live]
        if not len(rows):
            return
        keys = np.ascontiguousarray(rows["key"], dtype=np.int64)
        # the windows a row lies in, as one small number: between two
        # boundaries the rows take few such ranges (one, where the window
        # is a whole number of slides), so mostly the keys alone are sorted
        span = (lo - self._fired) * W + (hi - self._fired)
        one_span = span[0] == span[-1] and not (span != span[0]).any()
        order = (np.argsort(keys, kind="stable") if one_span
                 else np.lexsort((span, keys)))
        keys, span = keys[order], span[order]
        cut = np.diff(keys) != 0
        if not one_span:
            cut |= np.diff(span) != 0
        bnd = np.concatenate(([0], np.flatnonzero(cut) + 1))
        seg_slot = self._slotmap.lookup(keys[bnd])
        seg_span = span[bnd]
        seg_rows = np.diff(np.concatenate((bnd, [len(keys)])))
        seg_vals = [None if ufunc is None else ufunc.reduceat(
            rows[field].astype(dt, copy=False)[order], bnd)
            for (_of, field, ufunc, dt, _ident) in self._parts]
        for code in ([int(span[0])] if one_span
                     else np.unique(seg_span).tolist()):
            # the groups of one range hold each key once: a plain indexed
            # update cannot meet a slot twice
            sel = slice(None) if one_span else np.flatnonzero(
                seg_span == code)
            slots = seg_slot[sel]
            for w in range(self._fired + code // W,
                           self._fired + code % W + 1):
                lane = w % W
                self._rows[slots, lane] += seg_rows[sel]
                for (of, _f, ufunc, _dt, _i), vals in zip(self._parts,
                                                          seg_vals):
                    if ufunc is not None:   # counts are the lanes' rows
                        acc = self._acc[of]
                        acc[slots, lane] = ufunc(acc[slots, lane], vals[sel])

    def _take_window(self, w: int):
        """Result rows of window ``w`` for the keys that hold rows in it
        (their lanes emptied), or None."""
        lane = w % self._W
        n = self._slotmap.n
        live = np.flatnonzero(self._rows[:n, lane])
        if not len(live):
            return None
        out = np.zeros(len(live), dtype=self._result_dtype)
        out["key"] = self._slotmap.keys[live]
        out["id"] = w
        out["ts"] = w * self._ts_slide + self._L - 1
        for (of, _f, ufunc, dt, ident) in self._parts:
            if ufunc is None:
                out[of] = self._rows[live, lane].astype(dt)
            else:
                out[of] = self._acc[of][live, lane]
                self._acc[of][live, lane] = ident
        self._rows[live, lane] = 0
        return out

    def _fire(self, now: int) -> list:
        """Fire every window that ends at or before ``now``, oldest first,
        send the progress row, retire the keys left without rows."""
        with profile.span("stream_fire"):
            upto = (now - self._L) // self._S + 1
            outs = []
            # only the open windows can hold rows
            for w in range(self._fired, min(upto, self._fired + self._W)):
                out = self._take_window(w)
                if out is not None:
                    outs.append(out)
            self._fired = upto
            self._next_end = upto * self._S + self._L
            n_rows = sum(len(o) for o in outs)
            self.stream_fires += 1
            self.stream_fire_rows += n_rows
            profile.add("stream_fires")
            profile.add("stream_fire_rows", n_rows)
            outs.append(progress_row(self._result_dtype, upto - 1,
                                     self._next_end - self._S))
        self._retire()
        return outs

    def _retire(self):
        """Forget the keys whose lanes are all empty: compact the slots."""
        n = self._slotmap.n
        with profile.span("key_retire"):
            keep = np.flatnonzero(self._rows[:n].any(axis=1))
            if len(keep) == n:
                return
            m = len(keep)
            self._rows[:m] = self._rows[keep]
            self._rows[m:n] = 0
            for (of, _f, ufunc, _dt, ident) in self._parts:
                if ufunc is not None:
                    self._acc[of][:m] = self._acc[of][keep]
                    self._acc[of][m:n] = ident
            if self._native:
                self._slotmap.keys[:m] = self._slotmap.keys[keep]
                self._slotmap.n = m
                self._reindex()
            else:
                self._slotmap.retain(keep)
            self.keys_retired += n - m
            profile.add("keys_retired", n - m)

    # -------------------------------------------------------------------- EOS

    def flush(self) -> np.ndarray:
        """EOS: every open window that holds rows fires, oldest first."""
        outs = [o for o in (self._take_window(w) for w in
                            range(self._fired, self._fired + self._W))
                if o is not None]
        self._fired += self._W
        self._next_end = self._fired * self._S + self._L
        self._retire()
        if not outs:
            return np.zeros(0, dtype=self._result_dtype)
        return outs[0] if len(outs) == 1 else np.concatenate(outs)


#: derived crossover cache, keyed by window shape — measured on THIS host
_SLIDING_THRESHOLD = {}
#: serialises the calibration benchmark: several farm workers
#: constructing LazySlidingCores concurrently would otherwise each run
#: the measurement under mutual contention and fit a skewed crossover
#: (ADVICE r4); the winner publishes the cached value the rest reuse
_THRESHOLD_LOCK = threading.Lock()


def derived_sliding_threshold(spec: WindowSpec = None,
                              force: bool = False) -> int:
    """Measure the per-key-core vs lane-core crossover cardinality on
    THIS host for this window SHAPE (r3 weak #4: the old hard-coded 512
    encoded the 1-core bench host; a multicore or faster host — or a
    denser window cadence, which multiplies the per-key core's
    per-window Python overhead — shifts the economics in an unmeasured
    direction).  Times both cores on a small synthetic stream of the
    given (win, slide) at two cardinalities, fits each as linear in key
    count, and solves for the intersection.  Cached per shape per
    process (~0.3-0.6 s once); mispredictions cost only throughput —
    LazySlidingCore migrates state if the stream later crosses whatever
    threshold this returns."""
    if spec is None:
        spec = WindowSpec(8, 2, WinType.CB)
    ck = (int(spec.win_len), int(spec.slide_len))
    if ck in _SLIDING_THRESHOLD and not force:
        return _SLIDING_THRESHOLD[ck]
    with _THRESHOLD_LOCK:
        if ck in _SLIDING_THRESHOLD and not force:
            return _SLIDING_THRESHOLD[ck]
        return _measure_sliding_threshold(ck)


def _measure_sliding_threshold(ck) -> int:
    import time as _t

    from .tuples import Schema, batch_from_columns
    from .winseq import WinSeqCore
    cal_spec = WindowSpec(ck[0], ck[1], WinType.CB)
    schema = Schema(value=np.int64)
    red = Reducer("sum")
    # enough rows that windows actually fire at the instance's cadence
    # for every probed cardinality, capped so wide-slide shapes keep the
    # one-off calibration under ~a second
    lo_k, hi_k = 64, 2048
    rows = max(4096, min(hi_k * 4 * ck[1], 1 << 17))

    def once(cls, nk):
        per = rows // nk
        ids = np.tile(np.arange(per, dtype=np.int64), nk)
        keys = np.repeat(np.arange(nk, dtype=np.int64), per)
        order = np.argsort(ids, kind="stable")   # interleave keys
        b = batch_from_columns(schema, key=keys[order], id=ids[order],
                               ts=ids[order], value=ids[order] % 97)
        best = None
        for _ in range(2):        # best-of: least interference
            core = cls(cal_spec, red)
            t0 = _t.perf_counter()
            core.process(b)
            core.flush()
            dt = _t.perf_counter() - t0
            best = dt if best is None else min(best, dt)
        return best

    pk_lo, pk_hi = once(WinSeqCore, lo_k), once(WinSeqCore, hi_k)
    vec_lo, vec_hi = (once(VecIncSlidingCore, lo_k),
                      once(VecIncSlidingCore, hi_k))
    # t(nk) = t_lo + b*(nk - lo_k) per core; the lines meet at
    # nk* = lo_k + (vec_lo - pk_lo) / (pk_b - vec_b)
    pk_b = (pk_hi - pk_lo) / (hi_k - lo_k)
    vec_b = (vec_hi - vec_lo) / (hi_k - lo_k)
    if pk_b <= vec_b:
        # per-key never loses ground with cardinality here (e.g. a many-
        # core host whose dict path scales): keep a high threshold so the
        # migration path still covers extreme cardinalities
        nk_star = hi_k
    else:
        nk_star = lo_k + (vec_lo - pk_lo) / (pk_b - vec_b)
    th = int(min(max(nk_star, 64), 8192))
    _SLIDING_THRESHOLD[ck] = th
    return th


class LazySlidingCore:
    """Defers the sliding-core choice to observed key cardinality: the
    per-key-group ``WinSeqCore`` wins at low key counts, the
    lane-vectorised ``VecIncSlidingCore`` above a crossover MEASURED on
    the running host (derived_sliding_threshold — on the 1-core bench
    host it lands between 256 and 1024 keys: 64 keys 2.9M vs 1.6M tps,
    16k keys 0.24M vs 4.0M).  The first chunk picks the initial core; if
    a key-clustered stream later crosses the threshold (e.g. per-key-
    partitioned replay whose first chunk carries few keys), the per-key
    core's state MIGRATES into the lane core — its NIC archives hold
    exactly the live rows the open-window lanes need — so the choice is
    never locked in.  Mispredictions cost only throughput, never
    correctness: both cores are differentially identical."""

    def __init__(self, spec: WindowSpec, winfunc, threshold: int = None,
                 **kw):
        self.spec = spec
        self.winfunc = winfunc
        self._kw = kw
        self._threshold = (int(threshold) if threshold is not None
                           else derived_sliding_threshold(spec))
        self._core = None
        self._perkey = False
        self.result_schema = Schema(**winfunc.result_fields)
        self._result_dtype = self.result_schema.dtype()
        self.is_nic = False

    def _pick(self, batch):
        nk = len(np.unique(batch["key"]))
        if nk >= self._threshold:
            self._core = VecIncSlidingCore(self.spec, self.winfunc,
                                           **self._kw)
        else:
            from .winseq import WinSeqCore
            self._core = WinSeqCore(self.spec, self.winfunc, **self._kw)
            self._perkey = True
        return self._core

    def _escalate(self):
        """Move the per-key core's live state into a fresh lane core:
        per-key scalars copy across (the slot registration recomputes the
        identical distribution math), and each open window's accumulator
        folds from the archive range the NIC core kept live (purge only
        ever runs below the last FIRED window's start, so open windows'
        rows are all present)."""
        old = self._core
        vec = VecIncSlidingCore(self.spec, self.winfunc, **self._kw)
        W = vec._W
        spec = self.spec
        if old._keys:
            keys = np.fromiter(old._keys.keys(), dtype=np.int64,
                               count=len(old._keys))
            slots = vec._slots_for(keys)
            for key, slot in zip(keys.tolist(), slots.tolist()):
                st = old._keys[key]
                vec._last_pos[slot] = st.last_pos
                vec._nfired[slot] = st.n_fired
                vec._ncreated[slot] = st.next_lwid
                vec._seen[slot] = st.next_lwid > st.n_fired
                vec._emit_ctr[slot] = st.emit_counter
                vec._marker_pos[slot] = st.marker_pos
                vec._marker_ts[slot] = st.marker_ts
                p = st.archive.positions
                rows = st.archive.rows
                for lw in range(st.n_fired, st.next_lwid):
                    lo = np.searchsorted(p, spec.win_start(lw)
                                         + st.initial_id, side="left")
                    hi = np.searchsorted(p, spec.win_end(lw)
                                         + st.initial_id, side="left")
                    if hi <= lo:
                        continue
                    lane = lw % W
                    seg = rows[lo:hi]
                    for (of, field, ufunc, dt, _ident) in vec._parts:
                        if ufunc is None:
                            vec._acc[of][slot, lane] = hi - lo
                        else:
                            vec._acc[of][slot, lane] = ufunc.reduce(
                                seg[field].astype(dt, copy=False))
                    vec._acc_ts[slot, lane] = int(seg["ts"][-1])
        vec.windows_fired_complete = old.windows_fired_complete
        self._core = vec
        self._perkey = False

    @property
    def windows_fired_complete(self):
        """The backing core's count (None where the input is not dense)."""
        if self._core is not None:
            return self._core.windows_fired_complete
        return 0 if self._kw.get("dense_positions") else None

    def process(self, batch):
        core = self._core
        if core is None:
            if len(batch) == 0:
                return np.zeros(0, dtype=self._result_dtype)
            core = self._pick(batch)
        out = core.process(batch)
        if self._perkey and len(core._keys) >= self._threshold:
            self._escalate()
        return out

    def flush(self):
        if self._core is None:
            return np.zeros(0, dtype=self._result_dtype)
        return self._core.flush()

    def use_incremental(self):
        return self  # both backing cores compute the monoid INC == NIC

    # -------------------------------------------------- keyed state migration
    # Sibling workers may have picked DIFFERENT backings (each decides on
    # its own first chunk): before migrating, control/rescale.py
    # harmonizes every involved LazySlidingCore onto one backing class
    # via ensure_backing — escalation is lossless (the per-key core's
    # archives rebuild the lane accumulators, see _escalate), the
    # reverse direction is not, so vec wins whenever any sibling runs it.

    #: both possible backings are host cores
    keyed_migratable = True

    def ensure_backing(self, vec: bool):
        if self._core is None:
            if vec:
                self._core = VecIncSlidingCore(self.spec, self.winfunc,
                                               **self._kw)
            else:
                from .winseq import WinSeqCore
                self._core = WinSeqCore(self.spec, self.winfunc,
                                        **self._kw)
                self._perkey = True
        elif vec and self._perkey:
            self._escalate()

    @property
    def backing_is_vec(self):
        """None before the first chunk, else whether the lane core runs."""
        return None if self._core is None else not self._perkey

    def keyed_state_keys(self):
        if self._core is None:
            return np.zeros(0, dtype=np.int64)
        return self._core.keyed_state_keys()

    def keyed_state_export(self, keys):
        return self._core.keyed_state_export(keys)

    def keyed_state_import(self, frag):
        return self._core.keyed_state_import(frag)
