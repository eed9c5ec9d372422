"""Win_Seq pattern: the sequential window core as a dataflow node
(reference win_seq.hpp — also the building block of every windowed farm).
"""

from __future__ import annotations

import numpy as np

from ..core.tuples import MARKER_FIELD
from ..core.windows import (PatternConfig, Role, WindowSpec, WinType,
                            check_dense_positions, check_fire_on)
from ..core.winseq import WinSeqCore
from ..ops.functions import WindowFunction, WindowUpdate, as_window_function, as_window_update
from ..runtime.node import Node, RuntimeContext
from ..utils import profile
from .basic import _Pattern


#: the span a stage of a Pane_Farm emits its results under (profiling on)
_STAGE_EMIT = {Role.PLQ: "pane_emit", Role.WLQ: "window_emit"}
#: result ids a stage-emit record lists (a batch of more names its first)
_EMIT_IDS = 64


class WinSeqNode(Node):
    """Runtime node driving a WinSeqCore."""

    #: svc folds rows into per-key window/ordering state BEFORE any
    #: raise, so a quarantined batch would leave that state partially
    #: mutated (silently wrong windows) — never quarantine under the
    #: dataflow-wide error_budget; fail fast (runtime/overload.py)
    quarantine_exempt = True
    #: recovery (docs/ROBUSTNESS.md): window state restores from an
    #: epoch snapshot — host cores by whole-core deep copy (archives,
    #: vecinc lanes, ordering buffers are all plain numpy/dict state),
    #: device cores via their own snapshot hooks (ring archive handle +
    #: host bookkeeping) — and supervised restart replays the journal
    recoverable = True

    #: a stream-time core's results of one fire leave in pieces of at most
    #: this many rows (``_emit_fires``); a pattern that knows its stage's
    #: ``flush_rows`` sets a sixteenth of it, which is this default's too
    burst_rows = 1 << 16
    #: set when a batch that ends in a marker has been served, for the farm
    #: emitter that waits for it (patterns/win_farm.py ``hands_over``); the
    #: farm gives its workers one each, every other node has none
    turn = None

    def __init__(self, core: WinSeqCore, name="win_seq"):
        super().__init__(name)
        self.core = core
        #: whether the core took this node's waker (``svc_init``)
        self._woken = False

    def svc_init(self):
        # a core whose ship threads hand results over between two chunks
        # (the native core, where it says so) has them wake this node, so
        # a finished result leaves now and not with the next input
        take = getattr(self.core, "set_waker", None)
        self._woken = bool(take is not None and self._wake is not None
                           and take(self._wake))

    def on_wake(self):
        if not self._woken:
            return
        core = self.core
        out = core.collect()
        if len(out):
            st = self.stats
            if st is not None:
                st.bump("windows_fired", len(out))
                st.counters["result_wakes"] = core.result_wakes
                st.counters["result_wake_rows"] = core.result_wake_rows
            self._emit_results(out)

    def checkpoint_prepare(self):
        """Device cores buffer fired windows in an async launch queue;
        at an epoch barrier their results pre-date the snapshot cut, so
        flush + drain them for emission first — per launch, keeping the
        emission seq numbering independent of harvest timing (host
        cores: no-op)."""
        drain = getattr(self.core, "checkpoint_drain_batches", None)
        if drain is None:
            return None
        with profile.span("checkpoint_drain"):
            return drain()

    def state_snapshot(self):
        with profile.span("state_export"):
            snap_fn = getattr(self.core, "state_snapshot", None)
            if snap_fn is not None:
                return snap_fn()
            import copy
            try:
                return {"core": copy.deepcopy(self.core)}
            except Exception as e:
                # a core holding native/device handles without its own
                # snapshot hooks cannot deep-copy — decline loudly so the
                # supervisor degrades to fail-like-seed for this node
                from ..runtime.node import SnapshotUnsupported
                raise SnapshotUnsupported(
                    f"{self.name}: core {type(self.core).__name__} is not "
                    f"deep-copyable ({type(e).__name__}: {e})") from e

    def state_restore(self, snap):
        # the native core's snapshot is a lazy handle object, not a
        # dict — anything that isn't the deep-copy form goes to the
        # core's own restore hook
        with profile.span("state_restore"):
            if isinstance(snap, dict) and "core" in snap:
                import copy
                self.core = copy.deepcopy(snap["core"])
            else:
                self.core.state_restore(snap)

    def svc(self, batch, channel=0):
        self._serve(batch)
        if (self.turn is not None and len(batch)
                and batch[MARKER_FIELD][-1]):
            self.turn.set()

    def _serve(self, batch):
        if self._recov is not None:
            # recovery mode + async device core: emit ONE batch per
            # completed launch, in launch order.  Launch boundaries are
            # count-triggered (deterministic); how many launches a given
            # poll() harvests is wall-clock — concatenating them per svc
            # (the seed path) would make replayed emission grouping
            # diverge from the original run's and break the per-edge
            # seq dedup (a split regroup would double-deliver windows).
            pb = getattr(self.core, "process_batches", None)
            if pb is not None:
                self._emit_each(pb(batch), triggering=True)
                return
        st = self.stats
        if st is not None and len(batch) and batch[MARKER_FIELD][-1]:
            st.bump("progress_seen")
        out = self.core.process(batch)
        if len(out):
            # triggering vs non-triggering split (win_seq.hpp:479-501)
            if st is not None:
                st.bump("windows_fired", len(out))
                st.bump("triggering_batches")
            self._emit_results(out)
        elif st is not None:
            st.bump("non_triggering_batches")

    def _emit_results(self, out):
        phase = (_STAGE_EMIT.get(getattr(self.core, "role", None))
                 if profile.ENABLED else None)
        if phase is not None:
            # a two-stage window pattern's hand-over, one record a batch of
            # results in ``launches.jsonl``: when a pane's partial left its
            # worker, when the window built from it had been handed on
            with profile.span(phase) as sp:
                sp.extra = {"key": int(out["key"][0]),
                            "ids": out["id"][:_EMIT_IDS].tolist(),
                            "rows": len(out)}
                self.emit(out)
        elif getattr(self.core, "fire_on", "key") == "stream":
            self._emit_fires(out)
        else:
            self.emit(out)

    def _emit_fires(self, out):
        """A stream-time core's output: every fire's results, each closed
        by its progress row.  One fire releases every live key's window at
        once, so it leaves in pieces of at most ``burst_rows`` rows — the
        nodes behind work on the first while this one cuts the next, instead
        of one 10^6-row batch stalling each in turn — and a progress row is
        always the last row of its piece."""
        ends = np.flatnonzero(out[MARKER_FIELD]) + 1
        if not len(ends) or ends[-1] != len(out):
            ends = np.append(ends, len(out))        # the end-of-stream flush
        lo = pieces = 0
        for hi in ends.tolist():
            while lo < hi:
                step = min(hi, lo + self.burst_rows)
                self.emit(out[lo:step])
                lo = step
                pieces += 1
        n_progress = int(out[MARKER_FIELD].sum())
        profile.add("progress_sent", n_progress)
        profile.add("burst_batches", pieces)
        st = self.stats
        if st is not None:
            st.bump("progress_sent", n_progress)
            st.bump("burst_batches", pieces)
            self._stream_counters(st)

    def _stream_counters(self, st):
        """A stream-time core's own counts onto the node's (the cores report
        what they have: a host core holds no row back, the native core
        retires no key)."""
        self._core_counters(st, (
            "keys_live", "keys_live_peak", "keys_retired", "stream_fires",
            "stream_fire_rows", "late_rows", "rows_out_of_order",
            "rows_reinserted", "rows_held_peak", "watermark_fires"))

    def _core_counters(self, st, names):
        """Copies those of the core's own counts it has onto the node's."""
        for name in names:
            value = getattr(self.core, name, None)
            if value is not None:
                st.counters[name] = int(value)

    def _emit_each(self, outs, triggering=False):
        fired = 0
        for out in outs:
            if len(out):
                fired += len(out)
                self.emit(out)
        if self.stats is not None:
            if fired:
                self.stats.bump("windows_fired", fired)
                if triggering:
                    self.stats.bump("triggering_batches")
            elif triggering:
                self.stats.bump("non_triggering_batches")

    def eosnotify(self):
        if self.stats is not None:
            # what a core says of its paths, now that every row is in: the
            # bytes one took in a native core's archive, the rows its bulk
            # path took, the chunks a stream-time host core folded natively,
            # the windows a stage over dense positions fired with their last
            # row, those a farm worker closed on a marker and not on a row
            # (the flush fires only what the stream's end left open)
            self._core_counters(self.stats, (
                "archive_row_bytes", "fast_rows", "fold_native_batches",
                "windows_fired_complete", "windows_fired_by_progress"))
        if self._recov is not None:
            fb = getattr(self.core, "flush_batches", None)
            if fb is not None:
                self._emit_each(fb())
                return
        out = self.core.flush()
        if self.stats is not None:
            # a container-valued device result's slots (win_seq_tpu.
            # _count_slots), the flush's results among them
            self._core_counters(self.stats, (
                "pane_results", "pane_points_kept", "pane_overflow"))
        if len(out):
            if self.stats is not None:
                self.stats.bump("windows_fired", len(out))
            self._emit_results(out)


def window_cores(df) -> list:
    """The window cores of a built Dataflow, chained stages included —
    what a run asserts on to know WHICH core did the work (a ``*TPU``
    stage can legitimately route to a host core, make_core_for)."""
    cores = []

    def walk(node):
        for stage in getattr(node, "stages", ()):
            walk(stage)
        if isinstance(node, WinSeqNode):
            cores.append(node.core)

    for node in df.nodes:
        walk(node)
    return cores


class WinSeq(_Pattern):
    """Sequential window pattern (parallelism is always 1; farms build
    parallelism around it, win_farm.hpp:134)."""

    def __init__(self, winfunc, win_len: int, slide_len: int,
                 win_type: WinType = WinType.CB, name="win_seq",
                 incremental: bool = None, result_fields=None,
                 config: PatternConfig = None, role: Role = Role.SEQ,
                 map_indexes=(0, 1), result_ts_slide: int = None,
                 fire_on: str = "key", holdback: int = 0,
                 dense_positions: bool = False):
        super().__init__(name, parallelism=1)
        self.spec = WindowSpec(win_len, slide_len, win_type)
        self.result_ts_slide = result_ts_slide
        #: what the pattern that wires this stage knows of its input (a
        #: Pane_Farm of its own pane stream; no user option): every position
        #: of each window arrives once and in order, so the cores fire a
        #: window with its last row (core/winseq.py)
        check_dense_positions(dense_positions, self.spec, fire_on)
        self.dense_positions = bool(dense_positions)
        #: ``"key"``: a key's window closes on that key's next row (the
        #: reference's triggerer).  ``"stream"`` (time-based windows): on
        #: the stage's time, the highest ``ts`` taken in on any key; quiet
        #: keys are retired and a progress row follows every fire
        #: (core/vecinc.VecStreamCore, core/winseq.py).  ``holdback`` (in
        #: the unit of ``ts``) keeps the stage's watermark that far behind
        #: its clock, so rows up to that much out of order are all counted
        check_fire_on(fire_on, self.spec, config, role, holdback)
        self.fire_on = fire_on
        self.holdback = int(holdback)
        # resolve the function flavour (meta_utils.hpp signature deduction
        # becomes an explicit `incremental` switch)
        if incremental is True:
            winfunc = as_window_update(winfunc, result_fields)
        elif incremental is False or isinstance(winfunc, WindowFunction):
            winfunc = as_window_function(winfunc, result_fields)
        elif isinstance(winfunc, WindowUpdate):
            incremental = True
        else:
            winfunc = as_window_function(winfunc, result_fields)
        self.winfunc = winfunc
        self.incremental = bool(incremental)
        self.config = config
        self.role = role
        self.map_indexes = map_indexes

    def make_core(self) -> WinSeqCore:
        # Tumbling/sliding windows over a monoid reducer take the
        # vectorised multi-key core: identical INC semantics (== NIC for a
        # monoid), O(rows log rows) per chunk regardless of key
        # cardinality.  WF_NO_VECCORE=1 forces the reference per-key core
        # (debugging / differential runs).
        import os
        from ..core.vecinc import make_vec_core, vec_core_supported
        stream = ({"holdback": self.holdback}
                  if self.fire_on == "stream" else {})
        if self.dense_positions:
            stream["dense_positions"] = True
        if (vec_core_supported(self.spec, self.winfunc)
                and not os.environ.get("WF_NO_VECCORE")):
            return make_vec_core(
                self.spec, self.winfunc, fire_on=self.fire_on,
                config=self.config, role=self.role,
                map_indexes=self.map_indexes,
                result_ts_slide=self.result_ts_slide, **stream)
        core = WinSeqCore(self.spec, self.winfunc, config=self.config,
                          role=self.role, map_indexes=self.map_indexes,
                          result_ts_slide=self.result_ts_slide,
                          fire_on=self.fire_on, **stream)
        if self.incremental:
            core.use_incremental()
        return core

    def _make_replica(self, i):
        node = WinSeqNode(self.make_core(), f"{self.name}.{i}")
        node.ctx = RuntimeContext(1, 0, self.name)
        return node

    @property
    def result_schema(self):
        from ..core.tuples import Schema
        return Schema(**self.winfunc.result_fields)
