"""Win_Farm: window parallelism — windows are assigned round-robin to
workers, each worker running the sequential core with a private slide of
``slide * pardegree`` (reference win_farm.hpp:134-143).

The emitter multicasts each tuple to exactly the workers whose windows
contain it (wf_nodes.hpp:90-174); in the reference this uses a refcounted
shared wrapper to avoid copies — here batches are immutable arrays, so the
per-worker "copy" is a numpy boolean take of the batch (and the device-side
analog goes further: the archive slice is staged once, see ops/device).

At EOS the emitter replays each key's last tuple to ALL workers as an EOS
marker (wf_nodes.hpp:177-191) so every worker opens/fires the same trailing
windows Win_Seq would have.

One departure from wf_nodes.hpp, for time-based windows below the multicast
test (``WFEmitterNode.sends_progress`` says for which farms that can come
about): a row the reference never sends.  A worker closes window ``w`` on the
first row at or past its end, and under the reference's routing it gets the
key's next row only with its own next window — ``pardegree - 1`` window
lengths later on tumbling windows — or at EOS.  So when a batch takes a key
past the end of a window, every worker that owns such a window and was not
sent the key's newest row gets that row as a marker, the form of the EOS
replay (``_send_progress``): it is tracked, never archived and never folded,
and it promises what ``KeyedStreamState.filter`` has already enforced — no
later row of the key lies behind it — so the worker closes on it exactly what
the key's next row would have closed had it been routed there.  No result
changes, only the moment it leaves.  Count-based windows keep the reference's
path: an in-band marker overwrites the result ts of every count-based window
it falls below (core/winseq.py:_result_ts), which is right at EOS only.

Where the workers dispatch device launches (``WinFarm.hands_over``), the
emitter then stands still until each worker it sent such a row has served
it — the worker's turn.  The row sets off a launch whose host part is a few
milliseconds of interpreter work; a worker that had no row to take in used
to run it ahead of its own intake, and now runs it beside the emitter's
routing and its sibling's intake, three threads after one interpreter lock:
on the benchmark's chip host the launch took twice as long and slowed both
(PERF.md section 6, PR 49).  Given the turn, it runs alone and the stream is a
launch behind for the moment, as it was behind that worker's own launch.
"""

from __future__ import annotations

import threading

import numpy as np

from ..core.tuples import MARKER_FIELD, select_rows, take_rows
from ..core.windows import PatternConfig, Role, WindowSpec, WinType
from ..runtime.emitters import Collector, KeyedStreamState
from ..runtime.node import Node, RuntimeContext
from ..runtime.ordering import OrderingCore, OrderingMode
from ..utils import profile
from .basic import _Pattern
from .win_seq import WinSeq, WinSeqNode

_NEG_INF = np.int64(-(2 ** 62))


#: the longest an emitter stands still for one worker's turn: far over a
#: launch's host part (5-20 ms on the chip's host), so that a worker which
#: is itself held up (a full ring of launches, a failed graph) cannot hold
#: the stream for good
_TURN_WAIT_S = 0.25


class WFEmitterNode(Node):
    """Window-range multicast emitter (wf_nodes.hpp:40-195)."""

    quarantine_exempt = True    # framework shell: errors here fail fast
    shed_safe = True            # farm head: shedding drops raw stream rows
    #: recovery: the per-key last-tuple bookkeeping snapshots on the
    #: numpy path; the native keymap path raises SnapshotUnsupported
    #: (emitters.KeyedStreamState.state_snapshot)
    recoverable = True

    def state_snapshot(self):
        snap = self._state.state_snapshot()
        if snap is None:
            from ..runtime.node import SnapshotUnsupported
            raise SnapshotUnsupported(
                f"{self.name}: native keymap state is not snapshotable")
        return snap

    def state_restore(self, snap):
        self._state.state_restore(snap)

    def __init__(self, spec: WindowSpec, pardegree: int, id_outer=0, n_outer=1,
                 slide_outer=None, role: Role = Role.SEQ, name="wf_emitter",
                 turns=None):
        super().__init__(name)
        self.spec = spec
        self.pardegree = pardegree
        self.id_outer = id_outer
        self.n_outer = n_outer
        self.slide_outer = spec.slide_len if slide_outer is None else slide_outer
        self.role = role
        self.pos_field = "id" if spec.win_type is WinType.CB else "ts"
        self._state = KeyedStreamState(self.pos_field)
        self._progress = self.sends_progress(spec, pardegree)
        #: an event a worker, which that worker sets when it has served a
        #: batch that ends in a marker (``WinFarm.hands_over``); None: the
        #: emitter waits for nobody
        self._turns = turns

    @staticmethod
    def sends_progress(spec: WindowSpec, pardegree: int) -> bool:
        """Whether a farm emitter of ``pardegree`` workers over ``spec``
        ever sends a progress row (``_send_progress``; the module's
        docstring has the reasons): time-based windows, and fewer windows
        over a row than workers — where ``win_len // slide_len`` reaches
        ``pardegree`` every row at or past the first window's end goes to
        every worker, so nobody is ever left out.  A matter of the graph's
        structure, which the farm reads too: it is the workers of such an
        emitter that count ``windows_fired_by_progress``."""
        return (spec.win_type is WinType.TB
                and spec.win_len // spec.slide_len < pardegree)

    def _initial_id(self, keys: np.ndarray) -> np.ndarray:
        first_gwid = (self.id_outer - (keys % self.n_outer) + self.n_outer) % self.n_outer
        init = first_gwid * self.slide_outer
        if self.role in (Role.WLQ, Role.REDUCE):
            init = np.zeros_like(init)
        return init

    def svc(self, batch, channel=0):
        # marker absorption + out-of-order drop (wf_nodes.hpp:104-121)
        batch = self._state.filter(batch)
        every = len(batch) and self._route(batch)
        if self._progress and not every:
            self._send_progress()

    def _route(self, batch) -> bool:
        """Send each row to the workers whose windows hold it; True where
        that was every row to every worker."""
        spec = self.spec
        pos = self._state.pos_cache   # contiguous copy filter already made
        if pos is None:
            pos = batch[self.pos_field].astype(np.int64)
        keys = batch["key"]
        if self.n_outer == 1:
            rel = pos          # non-nested: _initial_id is identically 0
        else:
            rel = pos - self._initial_id(keys)
        keep = rel >= 0
        if spec.is_hopping:
            keep &= spec.in_any_window(np.maximum(rel, 0))
        if not np.all(keep):
            batch = select_rows(batch, keep)
            rel = rel[keep]
            keys = keys[keep]
        if len(batch) == 0:
            return False
        # window range per row (wf_nodes.hpp:134-157)
        first_w = spec.first_win_containing(rel)
        last_w = spec.last_win_containing(rel)
        count = last_w - first_w + 1
        n = self.pardegree
        # steady state of sliding windows (win > slide): every row belongs
        # to >= pardegree windows, so every worker gets every row — detect
        # it once and multicast the SAME array instead of gathering a full
        # copy per worker (workers only read; ~2x the stream size saved
        # per batch on the pipe benchmark)
        if count.min() >= n:
            for d in range(n):
                self.emit_to(d, batch)
            return True
        start_dst = (keys & (n - 1)) if n & (n - 1) == 0 else keys % n
        for d in range(n):
            # worker d gets the row iff some w in [first, first+min(count,n))
            # satisfies (key%n + w) % n == d
            r = (d - start_dst - first_w) % n
            m = (count >= n) | (r < count)
            sub = select_rows(batch, m)
            if len(sub):
                self.emit_to(d, sub)
        return False

    def _send_progress(self):
        """A key whose position the batch took past the end of a window:
        the window's worker closes it on a row at or past that end, and
        gets the key's next one only where it also holds a later window of
        its own.  So every worker that owns a window the key has just left
        and was NOT sent the key's newest row gets that row as a marker.
        Read off what ``filter`` holds of each distinct key: a batch that
        passes no end pays these few array operations a key, none a row."""
        state = self._state
        slots = state.key_slots
        if slots is None:
            return
        spec = self.spec
        prev, new = state.key_prev, state.key_now
        if self.n_outer > 1:
            init = self._initial_id(state.last_keys(slots))
            new, prev = new - init, prev - init
        lo, hi = spec.fired_before(prev), spec.fired_before(new)
        passed = np.flatnonzero(hi > lo)
        if not len(passed):
            return
        rows = state.last_rows(slots[passed])
        new, lo, hi = new[passed], lo[passed], hi[passed]
        # whom _route sent the key's newest row: the workers of the windows
        # that hold it, where it lies in a window at all
        first_w = spec.first_win_containing(new)
        held = np.where(spec.in_any_window(new),
                        spec.last_win_containing(new) - first_w + 1, 0)
        rows[MARKER_FIELD] = True
        n = self.pardegree
        start_dst = rows["key"] % n
        turns = self._turns
        sent, told = 0, []
        for d in range(n):
            # window w of a key is worker (key%n + w) % n's: d owns one of
            # [lo, hi) and none of [first_w, first_w + held)
            m = (((d - start_dst - lo) % n < hi - lo)
                 & ((d - start_dst - first_w) % n >= held))
            if m.any():
                if turns is not None:
                    turns[d].clear()
                    told.append(turns[d])
                self.emit_to(d, rows if m.all() else select_rows(rows, m))
                sent += int(np.count_nonzero(m))
        if sent:
            profile.add("progress_sent", sent)
            if self.stats is not None:
                self.stats.bump("progress_sent", sent)
        for turn in told:
            # the worker's turn (the module's docstring): blocked, not busy
            if self.stats is not None:
                self.stats.timed_wait(turn, _TURN_WAIT_S)
            else:
                turn.wait(_TURN_WAIT_S)

    def eosnotify(self):
        # per-key EOS markers to every worker (wf_nodes.hpp:177-191)
        markers = self._state.marker_batch()
        if markers is None:
            return
        for d in range(self.pardegree):
            self.emit_to(d, markers)


class WFCollectorNode(Node):
    """Ordered collector: per-key reorder over dense result ids
    (wf_nodes.hpp:401-468), fully vectorised — pending rows of ALL keys are
    one buffer; the releasable contiguous id-run per key is a segmented
    prefix test over a (key, id) lexsort, and each svc emits at most ONE
    batch (per-key tiny emits would turn 10^5 keys into 10^5 downstream
    svc calls)."""

    quarantine_exempt = True    # framework shell: errors here fail fast
    recoverable = True          # reorder state is plain numpy data

    def __init__(self, name="wf_collector"):
        super().__init__(name)
        from ..core.slots import SlotMap
        self._slots = SlotMap(on_register=self._on_register)
        self._next = np.zeros(0, dtype=np.int64)   # slot -> next expected id
        self._pend_rows = None                     # structured array
        self._pend_slots = np.zeros(0, dtype=np.int64)

    def state_snapshot(self):
        return {
            "slots": self._slots.state_snapshot(),
            "next": self._next.copy(),
            "pend_rows": (None if self._pend_rows is None
                          else self._pend_rows.copy()),
            "pend_slots": self._pend_slots.copy(),
        }

    def state_restore(self, snap):
        self._slots.state_restore(snap["slots"])
        self._next = snap["next"].copy()
        self._pend_rows = (None if snap["pend_rows"] is None
                           else snap["pend_rows"].copy())
        self._pend_slots = snap["pend_slots"].copy()

    def _on_register(self, new_keys):
        self._next = np.concatenate(
            (self._next, np.zeros(len(new_keys), dtype=np.int64)))

    def svc(self, batch, channel=0):
        slots = self._slots.lookup(batch["key"].astype(np.int64, copy=False))
        if self._pend_rows is not None and len(self._pend_rows):
            # only slots present in this batch can make progress (release
            # needs new rows; _next only advances on release) — leave the
            # rest of the pending buffer untouched instead of re-sorting it
            touched = np.isin(self._pend_slots, slots)
            if touched.any():
                rows = np.concatenate(
                    (select_rows(self._pend_rows, touched), batch))
                slots = np.concatenate((self._pend_slots[touched], slots))
                unt = ~touched
                self._pend_rows = (select_rows(self._pend_rows, unt)
                                   if unt.any() else None)
                self._pend_slots = (self._pend_slots[unt] if unt.any()
                                    else np.zeros(0, dtype=np.int64))
            else:
                rows = batch
        else:
            rows = batch
        ids = rows["id"].astype(np.int64, copy=False)
        order = np.lexsort((ids, slots))
        s = slots[order]
        sid = ids[order]
        starts = np.concatenate(([0], np.flatnonzero(np.diff(s)) + 1))
        rank = np.arange(len(s), dtype=np.int64)
        rank -= np.repeat(starts, np.diff(np.concatenate((starts, [len(s)]))))
        ok = sid == self._next[s] + rank
        # release the per-segment all-ok prefix: rows before a segment's
        # first gap (segmented cumulative-bad == 0)
        bad_cum = np.cumsum(~ok)
        seg_base = np.repeat(bad_cum[starts] - (~ok[starts]),
                             np.diff(np.concatenate((starts, [len(s)]))))
        release = (bad_cum - seg_base) == 0
        if release.any():
            n_rel = np.add.reduceat(release, starts)
            u = s[starts]
            self._next[u] += n_rel
            out = take_rows(rows, order[release])
            keep = ~release
            held = take_rows(rows, order[keep]) if keep.any() else None
            held_slots = slots[order[keep]] if keep.any() else None
            self._stash(held, held_slots)
            self.emit(out)
        else:
            self._stash(take_rows(rows, order), s)

    def _stash(self, held, held_slots):
        """Park unreleased rows, joining any untouched pending buffer."""
        if held is None:
            return  # untouched pending (if any) already lives in _pend_rows
        if self._pend_rows is not None and len(self._pend_rows):
            self._pend_rows = np.concatenate((self._pend_rows, held))
            self._pend_slots = np.concatenate((self._pend_slots, held_slots))
        else:
            self._pend_rows = held
            self._pend_slots = held_slots


class _OrderedWorkerNode(WinSeqNode):
    """OrderingCore fused in front of a window core — the
    ff_comb(OrderingNode, Win_Seq) worker used behind multiple emitters
    (win_farm.hpp:157-162).  It does not act on an emitter's progress row:
    one emitter's promise says nothing of the other channels, and the merge
    sets every marker aside until the end of the stream, so such a worker
    closes a window on its own next row, as before them."""

    def __init__(self, core, n_channels, mode, name, per_key=False):
        super().__init__(core, name)
        # per_key=True for merges of per-key-renumbered producer streams
        # (LEVEL2 fusion); plain multi-emitter splits are globally
        # monotone per channel and keep the liveness-preserving global
        # watermark (see OrderingCore)
        self.ordering = OrderingCore(n_channels, mode,
                                     per_key_watermarks=per_key)

    def state_snapshot(self):
        merge = self.ordering.state_snapshot()
        if merge is None:
            from ..runtime.node import SnapshotUnsupported
            raise SnapshotUnsupported(
                f"{self.name}: native renumbering counters are not "
                "snapshotable")
        snap = super().state_snapshot()
        snap["ordering"] = merge
        return snap

    def state_restore(self, snap):
        self.ordering.state_restore(snap["ordering"])
        super().state_restore({k: v for k, v in snap.items()
                               if k != "ordering"})

    def svc_init(self):
        super().svc_init()
        if self.n_input_channels != self.ordering.n_channels:
            raise RuntimeError(
                f"{self.name}: wired with {self.n_input_channels} input "
                f"channels but ordering expects {self.ordering.n_channels} "
                "(n_emitters mismatch — results would buffer until EOS)")

    def svc(self, batch, channel=0):
        for merged in self.ordering.push(batch, channel):
            super().svc(merged)

    def on_channel_eos(self, channel):
        for merged in self.ordering.channel_eos(channel):
            super().svc(merged)

    def eosnotify(self):
        for merged in self.ordering.flush():
            WinSeqNode.svc(self, merged)
        super().eosnotify()


class WinFarm(_Pattern):
    """Window-parallel farm of sequential cores (win_farm.hpp)."""

    #: whether the emitter stands still while a worker serves a progress
    #: row (the module's docstring).  Not here: a host worker's window
    #: function is the worker's own work, long or short, and runs beside
    #: the intake as the farm means it to; the farm of device workers says
    #: True, where that work is a launch's host part and the device does
    #: the rest
    hands_over = False

    def __init__(self, winfunc, win_len, slide_len, win_type=WinType.CB,
                 pardegree=2, name="win_farm", incremental=None,
                 result_fields=None, ordered=True, n_emitters=1,
                 config: PatternConfig = None, role: Role = Role.SEQ,
                 dense_positions: bool = False):
        super().__init__(name, pardegree)
        self.spec = WindowSpec(win_len, slide_len, win_type)
        self.ordered = ordered
        self.n_emitters = n_emitters
        #: LEVEL2 fusion flips this: the fused upstreams emit per-key
        #: renumbered ids, so the workers' merge needs per-key watermarks
        self.ordering_per_key = False
        self.config = config or PatternConfig.plain(slide_len)
        self.role = role
        #: an event a worker where the emitter gives a worker its turn
        #: (``hands_over``, the module's docstring), else None
        self._turns = ([threading.Event() for _ in range(pardegree)]
                       if self.hands_over and WFEmitterNode.sends_progress(
                           self.spec, pardegree) else None)
        # worker template: private slide, nested PatternConfig
        # (win_farm.hpp:134-143)
        self._workers = []
        for i in range(pardegree):
            cfg = PatternConfig(
                id_outer=self.config.id_inner, n_outer=self.config.n_inner,
                slide_outer=self.config.slide_inner,
                id_inner=i, n_inner=pardegree, slide_inner=slide_len)
            self._workers.append(WinSeq(
                winfunc, win_len, slide_len * pardegree, win_type,
                name=f"{name}_wf.{i}", incremental=incremental,
                result_fields=result_fields, config=cfg, role=role,
                result_ts_slide=slide_len,
                # the emitter hands a worker every id of each of its windows
                dense_positions=dense_positions))

    @property
    def result_schema(self):
        return self._workers[0].result_schema

    def emitter(self):
        return WFEmitterNode(self.spec, self.parallelism,
                             id_outer=self.config.id_inner,
                             n_outer=self.config.n_inner,
                             slide_outer=self.config.slide_inner,
                             role=self.role, name=f"{self.name}.emitter",
                             # (one of several emitters gives no turn: a
                             # worker behind a merge sets its rows aside)
                             turns=(self._turns if self.n_emitters == 1
                                    else None))

    def collector(self):
        if self.ordered:
            return WFCollectorNode(name=f"{self.name}.collector")
        return Collector(name=f"{self.name}.collector")

    def _make_core(self, worker: WinSeq, i=0):
        """Core-factory hook: TPU farms override to build device cores
        (worker index `i` drives per-worker device placement)."""
        return worker.make_core()

    def _make_replica(self, i):
        core = self._make_core(self._workers[i], i)
        if (self.n_emitters == 1
                and WFEmitterNode.sends_progress(self.spec, self.parallelism)
                and hasattr(core, "windows_fired_by_progress")):
            core.windows_fired_by_progress = 0      # (a core that counts)
        if self.n_emitters > 1:
            mode = OrderingMode.ID if self.spec.win_type is WinType.CB else OrderingMode.TS
            node = _OrderedWorkerNode(core, self.n_emitters, mode,
                                      f"{self.name}.{i}",
                                      per_key=self.ordering_per_key)
        else:
            node = WinSeqNode(core, f"{self.name}.{i}")
            if self._turns is not None:
                node.turn = self._turns[i]
        node.ctx = RuntimeContext(self.parallelism, i, self.name)
        return node
