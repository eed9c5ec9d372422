"""Win_Join_TPU: a window stage over TWO sides -- an inner equi-join of the
rows of each tumbling time-based window, on the device.

The reference has no join (its five window patterns reduce ONE keyed input);
this is the field's: NEXMark Q8's ``CoGroupByKey`` of two streams in
``FixedWindows``, Flink SQL's ``TUMBLE`` join.  The program's form of "two
inputs" is the one ``union_multipipes`` gives: one stream whose rows say
which side they are on (``side_field``) and carry, per side, the field that
is the join key.  For every window ``w`` and key ``k``: if the window holds
a LEFT row with key ``k``, one result per RIGHT row of the window with key
``k``, in the right rows' arrival order, carrying the declared fields of both
rows, ``id`` = ``w``, ``key`` = ``k`` and as ``ts`` the later of the two
event times.  The left side is unique per key and window (a primary key); a
second left row of a key in one window is counted on the device and raises on
the host, as a result that does not fit its slots does.  Nothing is ever cut.

How it runs.  The worker stages each chunk into int32 columns (the join key,
the side, the time as an offset into its window, each carried field: the
declared ranges prove them exact) and ships them in rectangles of
``flush_rows`` rows to one ring row a column (``jit_wf_step_append``).  The
chunk that opens window ``w + 1`` closes ``w``: the launch that ships its
last rows also evaluates it (``jit_wf_step_multi`` bound to
``ops/join.join_function``: two sorts that carry the rows' fields, two
running maxima) and
returns the matches compacted into ``max_results`` slots with their true
count.  The rings start again at offset 0 for the next window: a window
never waits for a rebase.  A watcher thread wakes the node when the result
is there (the Python resident core's own wake); the node unpacks it into
result rows in pieces of at most ``flush_rows``.

What it refuses, by name, at construction: a sliding or hopping window, a
count-based window, a left side that is not unique (a many-to-many join),
``fire_on="stream"``, ``max_delay_ms``, a mesh, a degree above 1, a key or a
carried field whose declared range int32 does not hold; and, when the graph
is built, ``recovery=``.
"""

from __future__ import annotations

import numpy as np

from ..core.tuples import MARKER_FIELD, Schema
from ..core.windows import WindowSpec, WinType
from ..ops import join as _join
from ..runtime.node import RuntimeContext
from ..utils import profile
from .basic import _Pattern
from .win_seq import WinSeqNode
from .win_seq_tpu import (JaxWindowFunction, _ResultWatch, plan_core,
                          resolve_worker_device)

_I32 = np.iinfo(np.int32)


def _refuse(name, what, instead):
    raise ValueError(f"WinJoinTPU {name!r}: {what} is not supported: "
                     f"{instead}")


def _int32_range(name, what, rng):
    """``(lo, hi)`` of a declared half-open range, refused unless every
    value of it is one an int32 ring holds apart from the two the step
    keeps for itself."""
    if rng is None or len(rng) != 2:
        raise ValueError(f"WinJoinTPU {name!r}: {what} needs a declared "
                         "range (lo, hi): the device holds it as int32")
    lo, hi = int(rng[0]), int(rng[1])
    if not (_I32.min < lo < hi <= _I32.max):
        raise ValueError(
            f"WinJoinTPU {name!r}: the declared range [{lo}, {hi}) of {what} "
            f"does not fit the device's int32 rings (({_I32.min}, "
            f"{_I32.max}))")
    return lo, hi


class WinJoinCore(_ResultWatch):
    """The join worker's state: the open window's rows on their way into the
    device rings, and the launches whose results are on their way back."""

    #: control-plane live rescale declined: the window lives in this
    #: worker's rings
    keyed_migratable = False

    def __init__(self, spec: WindowSpec, *, side_field, left, right,
                 key_range, left_fields, right_fields, field_ranges,
                 window_rows, max_results, flush_rows, name="win_join_tpu",
                 device=None, depth=4, worker_index=0):
        from ..ops.device import _bucket, _bucket_fine
        from ..ops.resident import make_executor
        self.spec = spec
        self.name = name
        self.win = int(spec.win_len)
        self.side_field = side_field
        (self.left_value, self.left_key) = left
        (self.right_value, self.right_key) = right
        self.left_fields = tuple(left_fields)
        self.right_fields = tuple(right_fields)
        self.key_range = key_range
        carried = tuple(dict.fromkeys(self.right_fields + self.left_fields))
        self.field_ranges = {f: field_ranges[f] for f in carried}
        #: the rings, in the order the step takes them
        self.fields = (_join.KEY, _join.SIDE, _join.TIME) + carried
        rings = {f: np.dtype(np.int32) for f in self.fields}
        #: what a column crosses the wire as
        self._wire = dict(rings, **{_join.SIDE: np.dtype(np.int8)})
        self.flush_rows = int(flush_rows)
        #: a rectangle's columns as the rings take it
        self._rb = _bucket(self.flush_rows)
        self.window_rows = int(window_rows)
        #: the slots of a window's result: what is asked of the device
        self.cap = _bucket_fine(int(max_results))
        self.fn = JaxWindowFunction(
            _join.join_function(self.left_fields, self.right_fields,
                                self.cap),
            fields=self.fields,
            result_fields=dict(
                {c: np.dtype((np.int32, (self.cap,)))
                 for c in _join.result_columns(self.left_fields,
                                               self.right_fields)[:-2]},
                matches=np.int64, duplicates=np.int64),
            field_dtypes=rings, count_field="matches",
            window_rows=self.window_rows)
        plan = plan_core(spec, self.fn, use_resident=True)
        self.executor = make_executor(
            plan.family, self.fields, (), rings, jax_fn=self.fn,
            device=resolve_worker_device(device, worker_index), depth=depth,
            row_floor=1)
        self.executor.handed = "svc"
        self.executor.reset(1, _bucket(self.window_rows + self._rb))
        self._init_watch(worker_index)
        #: the number its launches' spans carry as their ship thread's
        self._shard = int(worker_index)
        payload = {f: np.int64 for f in self.right_fields + self.left_fields}
        self.result_schema = Schema(**payload)
        self._result_dtype = self.result_schema.dtype()
        self._wid = None          # the open window
        self._fill = 0            # its rows in the rings
        self._n_pend = 0          # ... and in `_buf`, on their way
        self._buf = None
        self._sides = [0, 0]      # the open window's left and right rows
        # what a node's log and the profile counters say of the join
        self.join_windows = 0
        self.join_left_rows = self.join_right_rows = 0
        self.join_results = 0
        self.join_slots_asked = self.join_slots_filled = 0
        self.join_refused = 0
        self.late_rows = 0

    # ---------------------------------------------------------------- staging

    def process(self, batch) -> list:
        """Take one chunk in; the launches whose results are ready, for
        :meth:`results`."""
        if len(batch):
            with profile.span("join_stage"):
                self._stage(batch)
        return self.executor.poll()

    def _stage(self, batch):
        wids = batch["ts"] // self.win
        first = int(wids[0])
        if first == int(wids[-1]) and not np.any(wids != first):
            self._take(first, batch)
            return
        cuts = np.flatnonzero(np.diff(wids)) + 1
        lo = 0
        for hi in (*cuts.tolist(), len(batch)):
            self._take(int(wids[lo]), batch[lo:hi])
            lo = hi

    def _take(self, wid, rows):
        """Rows of one window: they close the open one if theirs is later,
        and are dropped as late if theirs has closed."""
        real = ~rows[MARKER_FIELD]        # a marker moves time, nothing else
        if self._wid is None:
            self._wid = wid
        elif wid > self._wid:
            self._close()
            self._wid = wid
        elif wid < self._wid:
            n = int(np.count_nonzero(real))
            self.late_rows += n
            profile.add("late_rows", n)
            return
        side = rows[self.side_field]
        is_l = side == self.left_value
        is_r = side == self.right_value
        keep = (is_l | is_r) & real
        if not keep.all():
            idx = np.flatnonzero(keep)
            rows, is_l, is_r = rows[idx], is_l[idx], is_r[idx]
        if not len(rows):
            return
        jk = np.where(is_l, rows[self.left_key], rows[self.right_key])
        self._check_range("the join key", jk, self.key_range)
        cols = {_join.KEY: jk, _join.SIDE: is_r,
                _join.TIME: rows["ts"] - wid * self.win}
        for f, rng in self.field_ranges.items():
            cols[f] = rows[f]
            self._check_range(f"the carried field {f!r}", cols[f], rng)
        n_l = int(np.count_nonzero(is_l))
        self._sides[0] += n_l
        self._sides[1] += len(rows) - n_l
        self._pend(cols, len(rows))

    def _check_range(self, what, vals, rng):
        lo, hi = int(vals.min()), int(vals.max())
        if lo < rng[0] or hi >= rng[1]:
            raise ValueError(
                f"WinJoinTPU {self.name!r}: {what} holds {lo}..{hi}, outside "
                f"its declared range [{rng[0]}, {rng[1]}): the device's "
                "int32 ring would not hold it exactly")

    def _pend(self, cols, n):
        """`n` staged rows into the rectangles on their way; a full one is
        shipped."""
        at = 0
        while at < n:
            if self._buf is None:
                self._buf = self._rectangles()
            m = min(n - at, self.flush_rows - self._n_pend)
            for f, col in cols.items():
                self._buf[f][0, self._n_pend:self._n_pend + m] = \
                    col[at:at + m]
            self._n_pend += m
            at += m
            if self._n_pend == self.flush_rows:
                self._ship()

    def _take_launch(self):
        """The host's part of a launch before the transfer, under the name
        the other cores give theirs: room in the rings for one more
        rectangle (grown on the device where a window outgrows what was
        declared), the rectangle itself and where it goes.  Returns the
        launch's tag -- its id, this worker as its ship thread, no cause --
        and the offset."""
        tag = (profile.next_id(), self._shard, None)
        with profile.span("launch_take", *tag):
            ex = self.executor
            while self._fill + self._rb > ex.cap:
                ex.grow(2 * ex.cap)
            if self._buf is None:
                self._buf = self._rectangles()
            return tag, np.array([self._fill], dtype=np.int64)

    def _rectangles(self):
        # fresh ones a launch: the last launch's may still be read
        return {f: np.zeros((1, self.flush_rows), dtype=dt)
                for f, dt in self._wire.items()}

    def _ship(self):
        """The rectangle on its way, appended to the rings."""
        tag, offs = self._take_launch()
        self.executor.append(self._buf, offs, tag=tag)
        self._fill += self._n_pend
        self._buf, self._n_pend = None, 0

    def _close(self):
        """Close the open window: its last rows appended and the whole of
        it joined, in one launch.  A window without a row launches
        nothing."""
        n = self._fill + self._n_pend
        (n_left, n_right), self._sides = self._sides, [0, 0]
        wid = self._wid
        if n == 0:
            return
        tag, offs = self._take_launch()
        ex = self.executor
        one, zero = np.ones(1, dtype=np.int64), np.zeros(1, dtype=np.int64)
        ex.launch((wid, n_left, n_right), self._buf, offs, zero, zero,
                  n * one, wkeys=zero, wgwids=wid * one, tag=tag)
        if self._waker is not None:
            self._watch(ex._last_out)
        self._buf, self._n_pend, self._fill = None, 0, 0
        self.join_windows += 1
        self.join_left_rows += n_left
        self.join_right_rows += n_right
        self.join_slots_asked += self.cap
        profile.add("join_windows")
        profile.add("join_left_rows", n_left)
        profile.add("join_right_rows", n_right)
        profile.add("join_slots_asked", self.cap)

    # ---------------------------------------------------------------- harvest

    def collect(self) -> list:
        """The launches that became ready since the node thread last
        looked (``WinJoinNode.on_wake``), for :meth:`results`."""
        return self._poll_woken()

    def flush(self) -> list:
        """The stream's end closes the open window; every launch is waited
        for."""
        if self._wid is not None:
            self._close()
            self._wid = None
        harvested = self.executor.drain()
        self._stop_watcher()
        return harvested

    def results(self, harvested):
        """The result rows of harvested launches, window by window, in
        pieces of at most ``flush_rows`` rows (a window's 10^7 results do
        not cross the engine as one batch).  Raises, before any row of the
        window leaves, where its left side was not unique or its result does
        not fit its slots."""
        names = _join.result_columns(self.left_fields, self.right_fields)
        carried = names[:-4]
        for (wid, n_left, n_right), outs in harvested:
            cols = dict(zip(names, outs))
            count = int(cols["matches"][0])
            dup = int(cols["duplicates"][0])
            if dup:
                raise ValueError(
                    f"WinJoinTPU {self.name!r}: window {wid} holds {dup} left "
                    f"rows whose key another left row of the window has (of "
                    f"{n_left}): the left side is unique per key and window; "
                    "a many-to-many join is not supported")
            if count > self.cap:
                self.join_refused += 1
                profile.add("join_refused")
                raise ValueError(
                    f"WinJoinTPU {self.name!r}: window {wid} joins {count} "
                    f"rows ({n_left} left, {n_right} right), over the "
                    f"{self.cap} slots of its result: declare max_results "
                    "for such a window; a truncated result is never handed "
                    "on")
            self.join_results += count
            self.join_slots_filled += count
            profile.add("join_results", count)
            profile.add("join_slots_filled", count)
            base = wid * self.win
            for lo in range(0, count, self.flush_rows):
                with profile.span("join_unpack"):
                    hi = min(lo + self.flush_rows, count)
                    out = np.zeros(hi - lo, dtype=self._result_dtype)
                    out["key"] = cols[_join.KEY][0, lo:hi]
                    out["id"] = wid
                    out["ts"] = base + cols[_join.TIME][0, lo:hi]
                    for f in carried:
                        out[f] = cols[f][0, lo:hi]
                yield out


class WinJoinNode(WinSeqNode):
    """Runtime node driving a :class:`WinJoinCore`: a window's result
    leaves as the pieces the core unpacks it into."""

    #: the window lives in device rings nobody snapshots
    recoverable = False

    _COUNTERS = ("join_windows", "join_left_rows", "join_right_rows",
                 "join_results", "join_slots_asked", "join_slots_filled",
                 "join_refused", "late_rows")

    def svc_init(self):
        if self._recov is not None:
            _refuse(self.name, "recovery=", "the window's rows live in "
                    "device rings that no checkpoint holds")
        super().svc_init()

    def _emit_windows(self, harvested, woken=False):
        if not harvested:
            return 0
        rows = 0
        for piece in self.core.results(harvested):
            rows += len(piece)
            self.emit(piece)
        st = self.stats
        if st is not None:
            st.bump("windows_fired", len(harvested))
            self._core_counters(st, self._COUNTERS)
        if woken:
            self.core._count_wakes(len(harvested), rows)
            if st is not None:
                st.counters["result_wakes"] = self.core.result_wakes
                st.counters["result_wake_rows"] = self.core.result_wake_rows
        return rows

    def on_wake(self):
        if self._woken:
            self._emit_windows(self.core.collect(), woken=True)

    def _serve(self, batch):
        rows = self._emit_windows(self.core.process(batch))
        if self.stats is not None:
            self.stats.bump("triggering_batches" if rows
                            else "non_triggering_batches")

    def eosnotify(self):
        self._emit_windows(self.core.flush())
        if self.stats is not None:
            self._core_counters(self.stats, self._COUNTERS)


class WinJoinTPU(_Pattern):
    """Tumbling-window inner equi-join of two sides of one stream, on the
    device.  ``left`` and ``right`` are ``(value of side_field, join key
    field)``; ``left_fields`` / ``right_fields`` the fields of each row the
    result carries (int64 in the result; on the device int32, exact by the
    ``field_ranges`` declared for them, as the key is by ``key_range``:
    half-open ``(lo, hi)``); ``window_rows`` the rows of both sides a window
    holds and ``max_results`` the matches it may give (what the caller knows
    of the stream: they size the rings and the result's slots up front; a
    window over ``window_rows`` grows them, a result over ``max_results``
    raises).  The module docstring says what the result is and what is
    refused."""

    #: the results of a window follow those of the one before, and share
    #: its id
    ordered = True
    dense_ids = False

    def __init__(self, win_len, slide_len=None, win_type=WinType.TB, *,
                 side_field, left, right, key_range, left_fields=(),
                 right_fields=(), field_ranges=None, window_rows,
                 max_results=None, left_unique=True, flush_rows=1 << 20,
                 name="win_join_tpu", pardegree=1, device=None, depth=None,
                 mesh=None, max_delay_ms=None, fire_on="key"):
        super().__init__(name, parallelism=1)
        slide_len = win_len if slide_len is None else slide_len
        self.spec = WindowSpec(win_len, slide_len, win_type)
        if win_type is not WinType.TB:
            _refuse(name, "a count-based window", "the join runs over "
                    "time-based windows, which both sides share")
        if int(slide_len) != int(win_len):
            _refuse(name, f"a sliding or hopping window (slide {slide_len} "
                    f"over {win_len})", "the join runs over tumbling windows")
        if not left_unique:
            _refuse(name, "a many-to-many join (left_unique=False)",
                    "the left side is unique per key and window")
        if fire_on != "key":
            _refuse(name, f"fire_on={fire_on!r}", "a window closes on the "
                    "first row of the next one")
        if max_delay_ms is not None:
            _refuse(name, "max_delay_ms", "a window's result follows its "
                    "close, not the wall clock")
        if mesh is not None:
            _refuse(name, "a mesh", "the window's rings live on one device")
        if int(pardegree) != 1:
            _refuse(name, f"a degree of {pardegree}", "one worker holds the "
                    "window (routing both sides by the join key is not "
                    "built)")
        if int(win_len) > _I32.max:
            _refuse(name, f"a window of {win_len} time units", "a row's "
                    "time is held as an int32 offset into its window")
        left, right = tuple(left), tuple(right)
        if len(left) != 2 or len(right) != 2 or left[0] == right[0]:
            raise ValueError(
                f"WinJoinTPU {name!r}: left and right are (value of "
                f"{side_field!r}, join key field) pairs of two different "
                f"sides, got {left} and {right}")
        left_fields, right_fields = tuple(left_fields), tuple(right_fields)
        out = left_fields + right_fields
        taken = set(out) & {"key", "id", "ts", MARKER_FIELD, _join.KEY,
                            _join.SIDE, _join.TIME}
        if len(set(out)) != len(out) or taken:
            raise ValueError(
                f"WinJoinTPU {name!r}: the carried fields {out} must differ "
                "from each other, from the result's key, id and ts and from "
                "the step's own columns")
        ranges = dict(field_ranges or {})
        if int(window_rows) <= 0 or int(flush_rows) <= 0:
            raise ValueError(f"WinJoinTPU {name!r}: window_rows and "
                             "flush_rows must be positive")
        self._kw = dict(
            side_field=side_field, left=left, right=right,
            key_range=_int32_range(name, "the join key", key_range),
            left_fields=left_fields, right_fields=right_fields,
            field_ranges={f: _int32_range(name, f"the carried field {f!r}",
                                          ranges.get(f)) for f in out},
            window_rows=int(window_rows),
            max_results=int(window_rows if max_results is None
                            else max_results),
            flush_rows=int(flush_rows), name=name, device=device,
            depth=4 if depth is None else int(depth))

    def check_dataflow(self, df):
        """Called as the graph is built (runtime/farm.add_farm)."""
        if df.recovery is not None:
            _refuse(self.name, "recovery=", "the window's rows live in "
                    "device rings that no checkpoint holds")

    def make_core(self):
        return WinJoinCore(self.spec, **self._kw)

    @property
    def result_schema(self):
        return Schema(**{f: np.int64 for f in self._kw["right_fields"]
                         + self._kw["left_fields"]})

    def _make_replica(self, i):
        node = WinJoinNode(self.make_core(), f"{self.name}.{i}")
        node.ctx = RuntimeContext(1, 0, self.name)
        return node
