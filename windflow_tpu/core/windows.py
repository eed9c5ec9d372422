"""Window model: count/time windows, triggerer math, farm distribution math.

Re-derivation of the reference's window engine (reference ``window.hpp`` and
``basic.hpp:136``) in closed form so that it vectorises:

* Count-based (CB) window ``wid`` over a keyed substream whose first id is
  ``initial_id`` covers ids ``[initial_id + wid*slide, initial_id + wid*slide
  + win_len)`` and FIRES on the first id ``>= initial_id + wid*slide +
  win_len`` (reference ``window.hpp:63-66``).
* Time-based (TB) window ``wid`` covers ts ``[initial_ts + wid*slide,
  initial_ts + wid*slide + win_len)`` and fires on the first ts ``>=
  initial_ts + wid*slide + win_len`` (reference ``window.hpp:84-87``).

Instead of keeping one heap-allocated ``Window`` object with a closure per
open window, we keep *arithmetic*: for an in-order substream the set of open /
fired / created windows is a pure function of (next_lwid, max id seen), which
is what lets the bookkeeping run as array ops over whole batches.

``PatternConfig`` carries the two-level farm-distribution parameters
(outer x inner nesting) exactly as the reference does (``basic.hpp:136``,
consumed at ``win_seq.hpp:307-314``).
"""

from __future__ import annotations

import enum
import math
from dataclasses import dataclass

import numpy as np

from .tuples import MARKER_FIELD


class WinType(enum.Enum):
    CB = "count"  # count-based: windows defined over tuple ids
    TB = "time"   # time-based: windows defined over tuple timestamps


class Role(enum.Enum):
    """Role of a window core inside a composed pattern (basic.hpp:84)."""

    SEQ = "seq"        # standalone sequential core
    PLQ = "plq"        # pane-level query stage of Pane_Farm
    WLQ = "wlq"        # window-level query stage of Pane_Farm
    MAP = "map"        # map stage of Win_MapReduce
    REDUCE = "reduce"  # reduce stage of Win_MapReduce


class OptLevel(enum.IntEnum):
    """Graph-optimisation level (basic.hpp:94). In this framework the
    runtime fuses nodes dynamically, so levels only gate fusion choices."""

    LEVEL0 = 0
    LEVEL1 = 1
    LEVEL2 = 2


@dataclass(frozen=True)
class PatternConfig:
    """Two-level distribution parameters for nested farm workers.

    ``id_outer/n_outer/slide_outer`` describe this worker's position in the
    outer farm, ``id_inner/n_inner/slide_inner`` in the inner pattern
    (reference basic.hpp:136-160).  A plain Win_Seq uses (0,1,slide,0,1,slide).
    """

    id_outer: int = 0
    n_outer: int = 1
    slide_outer: int = 0
    id_inner: int = 0
    n_inner: int = 1
    slide_inner: int = 0

    @staticmethod
    def plain(slide_len: int) -> "PatternConfig":
        return PatternConfig(0, 1, slide_len, 0, 1, slide_len)

    def first_gwid(self, key: int) -> int:
        """gwid of the first window of `key` assigned to this worker
        (win_seq.hpp:307)."""
        no, ni = self.n_outer, self.n_inner
        a = (self.id_inner - (key % ni) + ni) % ni
        b = (self.id_outer - (key % no) + no) % no
        return a * no + b

    def initial_id(self, key: int, role: Role) -> int:
        """First id/ts of the keyed substream reaching this worker
        (win_seq.hpp:309-314)."""
        no, ni = self.n_outer, self.n_inner
        initial_outer = ((self.id_outer - (key % no) + no) % no) * self.slide_outer
        initial_inner = ((self.id_inner - (key % ni) + ni) % ni) * self.slide_inner
        if role in (Role.WLQ, Role.REDUCE):
            return initial_inner
        return initial_outer + initial_inner

    def gwid_stride(self) -> int:
        """gwids assigned to one worker advance by n_outer*n_inner
        (win_seq.hpp:346)."""
        return self.n_outer * self.n_inner


@dataclass(frozen=True)
class WindowSpec:
    """A sliding/tumbling/hopping window definition."""

    win_len: int
    slide_len: int
    win_type: WinType

    def __post_init__(self):
        if self.win_len <= 0 or self.slide_len <= 0:
            raise ValueError("window length and slide must be positive")

    @property
    def is_tumbling(self) -> bool:
        return self.win_len == self.slide_len

    @property
    def is_hopping(self) -> bool:
        return self.slide_len > self.win_len

    def pane_len(self) -> int:
        """Pane decomposition length: gcd(win, slide) (pane_farm.hpp:148)."""
        return math.gcd(self.win_len, self.slide_len)

    # ---- closed-form window arithmetic (all positions relative to
    # ---- initial_id of the substream; works elementwise on numpy arrays) ----

    def _div_slide(self, x):
        """Floor-divide by slide_len; a power-of-two slide rides an
        arithmetic right shift (floor semantics for negatives too) —
        int64 division was the WF emitter's second-largest per-batch cost
        (~19 ms/M rows vs ~2 ms shifted)."""
        s = int(self.slide_len)   # numpy-int slide_lens lack bit_length
        if s & (s - 1) == 0:
            return x >> (s.bit_length() - 1)
        return x // s

    def last_win_containing(self, pos):
        """Local id of the last window containing position `pos` (>=0).

        Sliding/tumbling: ceil((pos+1)/slide) - 1  (win_seq.hpp:324)
        Hopping:          floor(pos/slide)         (win_seq.hpp:327)
        """
        pos = np.asarray(pos, dtype=np.int64)
        if self.is_hopping:
            return self._div_slide(pos)
        return np.maximum(self._div_slide(pos + self.slide_len) - 1, -1)

    def first_win_containing(self, pos):
        """Local id of the first window containing `pos`, i.e.
        max(0, ceil((pos - win + 1)/slide)) for sliding (wf_nodes.hpp:138-144);
        for hopping the only candidate is floor(pos/slide)."""
        pos = np.asarray(pos, dtype=np.int64)
        if self.is_hopping:
            return self._div_slide(pos)
        # floor division handles the pos < win_len operand range (the
        # quotient is <= 0 exactly there), so clamping replaces the
        # two-branch where — one fewer full-array pass
        return np.maximum(
            self._div_slide(pos - self.win_len + self.slide_len),
            np.int64(0))

    def in_any_window(self, pos):
        """Hopping streams have gaps: positions outside every window are
        dropped (win_seq.hpp:330). Always true for sliding windows."""
        pos = np.asarray(pos, dtype=np.int64)
        if not self.is_hopping:
            return np.ones(pos.shape, dtype=bool)
        off = pos % self.slide_len
        return off < self.win_len

    def fired_before(self, pos):
        """Number of windows already FIRED once position `pos` has been seen:
        window w fires on the first pos >= w*slide + win, so the count is
        floor((pos - win)/slide) + 1 for pos >= win, else 0."""
        pos = np.asarray(pos, dtype=np.int64)
        return np.where(
            pos >= self.win_len,
            (pos - self.win_len) // self.slide_len + 1,
            np.int64(0),
        )

    def fired_through(self, pos):
        """Number of windows COMPLETE once position `pos` has been seen, on
        a stream in which every position of a window arrives exactly once
        and in order (``WinSeqCore(dense_positions=True)``): window w's last
        position is w*slide + win - 1, and nothing behind it can add to w.
        One position ahead of :meth:`fired_before`, the reference's rule,
        which has to wait for the first pos >= w*slide + win because a
        user's stream may repeat a position."""
        return self.fired_before(np.asarray(pos, dtype=np.int64) + 1)

    def win_start(self, lwid):
        return np.asarray(lwid, dtype=np.int64) * self.slide_len

    def win_end(self, lwid):
        """Exclusive end position of window `lwid`."""
        return np.asarray(lwid, dtype=np.int64) * self.slide_len + self.win_len


def check_stream_fire(spec: WindowSpec, config: PatternConfig, role: Role,
                      holdback: int = 0):
    """``fire_on="stream"`` is defined for time-based windows of a plain
    sequential worker (a Win_Seq, a Key_Farm's workers): every key's window
    ``w`` is ``[w*slide, w*slide + win)``, so one clock closes it for all.
    ``holdback`` (>= 0, in the unit of ``ts``) keeps the stage's watermark
    that far behind its clock.  Raises ``ValueError`` otherwise."""
    if int(holdback) < 0:
        raise ValueError(f"holdback is a span of time >= 0, not {holdback}")
    if spec.win_type is not WinType.TB:
        raise ValueError("fire_on='stream' needs time-based windows: a "
                         "count-based window has no time to close on")
    if role is not Role.SEQ or (config is not None and (
            config.n_outer != 1 or config.n_inner != 1)):
        raise ValueError("fire_on='stream' needs a plain sequential window "
                         "worker (Win_Seq, Key_Farm), not a nested or "
                         f"staged one (role {role}, config {config})")


def check_fire_on(fire_on: str, spec: WindowSpec, config: PatternConfig,
                  role: Role, holdback: int = 0):
    """The ``fire_on=`` / ``holdback=`` arguments of a window stage, checked
    once for the patterns and the cores: ``"key"`` (no hold-back: a key's own
    next row closes its window) or ``"stream"`` (:func:`check_stream_fire`)."""
    if fire_on not in ("key", "stream"):
        raise ValueError(f"fire_on is 'key' or 'stream', not {fire_on!r}")
    if fire_on == "stream":
        check_stream_fire(spec, config, role, holdback)
    elif holdback:
        raise ValueError("holdback= belongs to fire_on='stream': a key's "
                         "own next row closes its window otherwise")


def check_dense_positions(dense_positions: bool, spec: WindowSpec,
                          fire_on: str = "key"):
    """``dense_positions`` states that every position of each of a stage's
    windows reaches it exactly once and in order.  Only a count-based stage
    that fires on its key's rows can be told so: a timestamp may repeat or
    be skipped, and a stream-time stage closes on its clock."""
    if dense_positions and (spec.win_type is not WinType.CB
                            or fire_on != "key"):
        raise ValueError(
            "dense_positions describes a count-based window stage that "
            f"fires on its key's rows, not {spec.win_type} / "
            f"fire_on={fire_on!r}")


def start_stream_clock(core, ts0: int):
    """The first row a stream-time core takes in, at ``ts0``: the stage
    starts at window 0 unless its first watermark lies before time 0
    (:func:`run_stream_clock`)."""
    if core._clock_started:
        return
    core._clock_started = True
    spec = core.spec
    if ts0 - core.holdback < 0:
        first = (ts0 - core.holdback - spec.win_len) // spec.slide_len + 1
        core._fired = first
        core._next_end = first * spec.slide_len + spec.win_len


def run_stream_clock(core, batch: np.ndarray, fold) -> list:
    """One chunk through a stream-time core (``fire_on="stream"``): the
    stage's clock -- the highest ``ts`` taken in -- runs row by row, and its
    watermark is the clock less ``core.holdback``.  The real rows before the
    first row that takes the watermark to the end of the next window to
    fire (``core._next_end``) go to ``fold(rows, ts)``; then
    ``core._fire(watermark)`` fires what the watermark has passed, and the
    rest of the chunk is looked at again.  Marker rows move the clock and
    are folded nowhere.  A stage starts at window 0, as the per-key cores
    do, unless its first watermark lies before time 0: then window ``w`` is
    ``[w*S, w*S + L)`` for every integer ``w`` from the first one that
    watermark has not closed, so that no row at or past it loses a window.
    Returns the result batches ``fold`` and ``_fire`` returned, in order."""
    ts = np.ascontiguousarray(batch["ts"], dtype=np.int64)
    real = ~batch[MARKER_FIELD]
    hold = core.holdback
    outs = []
    lo, n = 0, len(batch)
    if n:
        start_stream_clock(core, int(ts[0]))
    while lo < n:
        hit = ts[lo:] >= core._next_end + hold
        cut = lo + int(np.argmax(hit)) if hit.any() else n
        if cut > lo:
            rows, at = batch[lo:cut], ts[lo:cut]
            if not real[lo:cut].all():
                keep = np.flatnonzero(real[lo:cut])
                rows, at = rows[keep], at[keep]
            if len(rows):
                outs.extend(fold(rows, at) or ())
        if cut < n:
            outs.extend(core._fire(int(ts[cut]) - hold))
        lo = cut
    return outs
