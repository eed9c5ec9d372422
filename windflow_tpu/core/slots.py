"""Shared key->dense-slot registry for vectorised per-key state.

The multi-key hot paths (VecIncTumblingCore, WFCollectorNode) keep per-key
state in parallel arrays indexed by a dense slot id.  This helper owns the
one subtle piece both need: a vectorised lookup that maps a chunk's key
column to slots, registering first-seen keys in first-appearance order and
maintaining a sorted view for ``np.searchsorted`` lookups.
"""

from __future__ import annotations

import numpy as np


def segments(sorted_vals: np.ndarray):
    """(starts, ends) of equal-value runs in a sorted array."""
    starts = np.concatenate(([0], np.flatnonzero(np.diff(sorted_vals)) + 1))
    ends = np.concatenate((starts[1:], [len(sorted_vals)]))
    return starts, ends


def segmented_excl_running_max(s: np.ndarray, p: np.ndarray,
                               starts: np.ndarray,
                               head_seed: np.ndarray) -> np.ndarray:
    """Per-segment EXCLUSIVE running max of `p` (segments = equal runs of
    the sorted `s`), seeded with `head_seed[i]` at segment i's head — the
    vectorised form of the reference's per-row running-max ordering check
    (win_seq.hpp:293-305), O(rows log rows) by Hillis-Steele doubling."""
    q = p.copy()
    q[starts] = np.maximum(q[starts], head_seed)
    sh = 1
    n = len(q)
    while sh < n:
        same = s[sh:] == s[:-sh]
        np.maximum(q[sh:], np.where(same, q[:-sh], q[sh:]), out=q[sh:])
        sh *= 2
    excl = np.empty(n, dtype=np.int64)
    excl[1:] = q[:-1]
    excl[starts] = head_seed
    return excl


class SlotMap:
    """Dense int slots for int64 keys; lookup is O(rows log keys).

    The sorted view is kept in two levels: a large one and a small one of
    the keys registered since the last merge.  A chunk that brings new keys
    inserts them into the small level only, and the levels merge when the
    small one passes an eighth of the large, so registration costs a
    bounded number of copies a key however many keys are live (one sorted
    view re-inserted on every chunk is O(live keys) a chunk: quadratic on a
    stream whose key space grows).  ``retain`` forgets every slot but the
    named ones and renumbers the rest densely, for owners that retire keys.
    """

    __slots__ = ("n", "keys", "_sorted_keys", "_sorted_slots",
                 "_new_keys", "_new_slots", "_on_register")

    #: the small level is merged once it holds more keys than this and ...
    _MERGE_MIN = 1024
    #: ... more than the large level's size divided by this
    _MERGE_SHARE = 8

    def __init__(self, on_register=None):
        self.n = 0
        self.keys = np.zeros(0, dtype=np.int64)      # slot -> key
        self._sorted_keys = np.zeros(0, dtype=np.int64)
        self._sorted_slots = np.zeros(0, dtype=np.int64)
        self._new_keys = np.zeros(0, dtype=np.int64)    # small level
        self._new_slots = np.zeros(0, dtype=np.int64)
        #: optional hook called with the (m,) array of newly registered keys
        #: (their slots are n-m .. n-1) — per-key init math goes here
        self._on_register = on_register

    def _register(self, new_keys: np.ndarray):
        uniq, first_idx = np.unique(new_keys, return_index=True)
        k = uniq[np.argsort(first_idx)]              # first-appearance order
        new_slots = np.arange(self.n, self.n + len(k), dtype=np.int64)
        if self.n + len(k) > len(self.keys):
            grown = np.empty(max(2 * len(self.keys), self.n + len(k), 16),
                             dtype=np.int64)
            grown[:self.n] = self.keys[:self.n]
            self.keys = grown
        self.keys[self.n:self.n + len(k)] = k
        self.n += len(k)
        order = np.argsort(k, kind="stable")
        ks, ss = k[order], new_slots[order]
        pos = np.searchsorted(self._new_keys, ks)
        self._new_keys = np.insert(self._new_keys, pos, ks)
        self._new_slots = np.insert(self._new_slots, pos, ss)
        if len(self._new_keys) > max(self._MERGE_MIN, len(self._sorted_keys)
                                     // self._MERGE_SHARE):
            self._merge()
        if self._on_register is not None:
            self._on_register(k)

    def _merge(self):
        """Fold the small level into the large one (O(keys))."""
        if not len(self._new_keys):
            return
        pos = np.searchsorted(self._sorted_keys, self._new_keys)
        self._sorted_keys = np.insert(self._sorted_keys, pos, self._new_keys)
        self._sorted_slots = np.insert(self._sorted_slots, pos,
                                       self._new_slots)
        self._new_keys = np.zeros(0, dtype=np.int64)
        self._new_slots = np.zeros(0, dtype=np.int64)

    def state_snapshot(self) -> dict:
        """Data-only snapshot (recovery layer): the registered keys and
        the sorted lookup view — the ``on_register`` hook is identity,
        not state, and stays bound to the live owner on restore."""
        self._merge()
        return {"n": self.n, "keys": self.keys[:self.n].copy(),
                "sorted_keys": self._sorted_keys.copy(),
                "sorted_slots": self._sorted_slots.copy()}

    def state_restore(self, snap: dict):
        self.n = snap["n"]
        self.keys = snap["keys"].copy()
        self._sorted_keys = snap["sorted_keys"].copy()
        self._sorted_slots = snap["sorted_slots"].copy()
        self._new_keys = np.zeros(0, dtype=np.int64)
        self._new_slots = np.zeros(0, dtype=np.int64)

    def reindex(self):
        """Rebuild the sorted view from ``keys[:n]``, for an owner that
        numbered slots behind its back (``VecStreamCore``'s native fold
        keeps a hash index of its own and leaves this view empty)."""
        order = np.argsort(self.keys[:self.n], kind="stable")
        self.state_restore({"n": self.n, "keys": self.keys,
                            "sorted_keys": self.keys[:self.n][order],
                            "sorted_slots": order})

    def _find(self, keys: np.ndarray):
        """``(slots, found)`` over both levels; ``slots`` is meaningful
        where ``found``."""
        slots = np.zeros(len(keys), dtype=np.int64)
        found = np.zeros(len(keys), dtype=bool)
        for sk, ss in ((self._sorted_keys, self._sorted_slots),
                       (self._new_keys, self._new_slots)):
            if not len(sk):
                continue
            idx = np.minimum(np.searchsorted(sk, keys), len(sk) - 1)
            hit = sk[idx] == keys
            if hit.all():
                return ss[idx], hit
            slots = np.where(hit, ss[idx], slots)
            found |= hit
        return slots, found

    def lookup(self, keys: np.ndarray) -> np.ndarray:
        """Slots for `keys` (int64 array), registering unseen keys."""
        slots, found = self._find(keys)
        if found.all():
            return slots
        new = np.flatnonzero(~found)
        self._register(keys[new])
        slots[new] = self._find(keys[new])[0]
        return slots

    def retain(self, keep: np.ndarray):
        """Forget every slot not in ``keep`` (ascending slot numbers); the
        kept slots are renumbered ``0 .. len(keep)-1`` in that order, so an
        owner compacts its slot-indexed arrays with ``a[keep]``.  O(keys),
        no sort: the sorted view is filtered in place."""
        self._merge()
        new_of = np.full(self.n, -1, dtype=np.int64)
        new_of[keep] = np.arange(len(keep), dtype=np.int64)
        renum = new_of[self._sorted_slots]
        live = renum >= 0
        self._sorted_keys = self._sorted_keys[live]
        self._sorted_slots = renum[live]
        self.keys = self.keys[keep]
        self.n = len(keep)
