"""The two-sided window step: an inner equi-join of the rows of one closed
window, on the device.

A window's rows of both sides lie in one stretch of the worker's rings
(patterns/win_join_tpu.py): the join key each row has on its side (``jk``),
its side (``side``: 0 left, 1 right), its event time as an offset into the
window (``t``) and the fields the result carries.  The function built here is
a ``JaxWindowFunction``'s: ``fn(keys, gwids, cols, mask)`` over ``(B, pad)``
gathers of those rings.  Per window it

* sorts the rows by ``(jk, side)``, their fields with them, so a key's left
  row stands directly before its right rows (the left side is unique per key:
  two left rows of one key are then neighbours, and are counted);
* finds for every sorted row the newest left row at or before it (running
  maxima of the left rows' keys and places) and calls a right row a match
  where that row's key is its own;
* sorts the matches back into arrival order, again with their fields, and
  keeps the first ``cap``: the right row's fields, the left row's fields,
  the key and the later of the two event times.

It returns the slots, the TRUE number of matches (beyond ``cap`` too: a
device function cannot raise, so the host does where the count passes the
cap) and the number of left rows whose key an earlier left row of the window
has.  Everything is int32: the pattern proves the ranges.  In a device trace
its operations read ``wf_join`` (inside the step's ``wf_udf``).
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

LEFT, RIGHT = 0, 1
#: the join key, the side and the time offset: the rings every join has
KEY, SIDE, TIME = "jk", "side", "t"
#: the first key a ring cannot hold: rows of neither side sort behind it
_NO_KEY = np.iinfo(np.int32).max
#: ... and what stands for "no left row yet" under the keys' running maximum
_NO_LEFT = np.iinfo(np.int32).min


def _join_one(left_fields, right_fields, cap, cols, mask):
    """One window's rows ``(pad,)`` a column -> its result columns.  A row's
    fields travel with it through both sorts as further operands: a gather
    costs the chip some 9 ns a cell, a sort's operand a fraction of that."""
    jk, side, t = cols[KEY], cols[SIDE], cols[TIME]
    pad = jk.shape[0]
    iota = jnp.arange(pad, dtype=jnp.int32)
    is_l = mask & (side == LEFT)
    is_r = mask & (side == RIGHT)
    k = jnp.where(is_l | is_r, jk, _NO_KEY)
    s = jnp.where(is_l, LEFT, jnp.where(is_r, RIGHT, 2)).astype(jnp.int32)
    carried = tuple(dict.fromkeys(right_fields + left_fields))
    ks, ss, ps, ts, *fs = lax.sort(
        (k, s, iota, t) + tuple(cols[f] for f in carried), num_keys=2)
    fs = dict(zip(carried, fs))
    left_s = ss == LEFT
    dup = jnp.sum(left_s[1:] & left_s[:-1] & (ks[1:] == ks[:-1]),
                  dtype=jnp.int32)
    # the newest left row at or before each sorted place: its key (the keys
    # ascend, so a running maximum holds it) and where it stands; a right
    # row whose key that row has is a match
    lkey = lax.cummax(jnp.where(left_s, ks, _NO_LEFT))
    at = jnp.maximum(lax.cummax(jnp.where(left_s, iota, -1)), 0)
    hit = (ss == RIGHT) & (lkey == ks)
    n = jnp.sum(hit, dtype=jnp.int32)
    # back into arrival order, the matches first, their fields with them
    outs = [fs[f] for f in right_fields] + [fs[f][at] for f in left_fields]
    outs += [ks, jnp.maximum(ts, ts[at])]
    _src, *outs = lax.sort((jnp.where(hit, ps, _NO_KEY),) + tuple(outs),
                           num_keys=1)
    if cap > pad:
        outs = [jnp.pad(o, (0, cap - pad)) for o in outs]
    live = jnp.arange(cap, dtype=jnp.int32) < n
    return tuple(jnp.where(live, o[:cap], 0) for o in outs) + (n, dup)


@functools.lru_cache(maxsize=None)
def join_function(left_fields: tuple, right_fields: tuple, cap: int):
    """The window function of a join that carries ``right_fields`` of the
    right row and ``left_fields`` of the left row into ``cap`` slots: ONE
    function object for each such shape, so that every pipeline given the
    same join finds the steps an earlier one compiled
    (ops/resident._FN_STEP_CACHE).  Outputs, in order: a ``(B, cap)`` column
    for each right field, each left field, the key and the later event time;
    then ``(B,)`` the matches and the duplicate left rows."""

    def fn(keys, gwids, cols, mask):
        with jax.named_scope("wf_join"):
            return jax.vmap(functools.partial(
                _join_one, left_fields, right_fields, cap))(cols, mask)

    return fn


def result_columns(left_fields, right_fields):
    """The names of :func:`join_function`'s outputs, in its order."""
    return (tuple(right_fields) + tuple(left_fields)
            + (KEY, TIME, "matches", "duplicates"))
