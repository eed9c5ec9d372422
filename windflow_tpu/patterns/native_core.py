"""Native-backed resident window core: C++ bookkeeping + device ring.

Same contract as ``ResidentWinSeqCore`` (process/flush producing result
batches), but the per-row window bookkeeping and staging-rectangle assembly
run in ``native/wf_native.cpp`` with the GIL released — the C++ hot loop the
reference runs per tuple (win_seq.hpp:268-474), feeding the same resident
executors (ops/resident.make_executor).  Hands the stream to the pure-Python
core when the payload field is not int64 (the native ABI ships int64
columns).
"""

from __future__ import annotations

import ctypes
import os
import queue as _queue
import sys
import threading
import time
import weakref

import numpy as np

from ..core.tuples import MARKER_FIELD, Schema, progress_row
from ..core.windows import PatternConfig, Role, WindowSpec, WinType
from ..ops.functions import NO_ARG_ID, ArgReducer, Reducer
from ..utils import profile

_ROLE_CODE = {Role.SEQ: 0, Role.PLQ: 1, Role.WLQ: 2, Role.MAP: 3,
              Role.REDUCE: 4}
_WIRE_DTYPES = (np.int8, np.int16, np.int32, np.int64)

#: adaptive launch coalescing (wf_launch_coalesce): keep at most this many
#: dispatches in flight un-serviced; beyond it a ship thread holds, so the
#: C++ queue deepens and queued launches fuse into fewer, larger dispatches
#: (each dispatch costs one launch service, so under stall fewer of them win)
_DISPATCH_WINDOW = 8
#: the share of a ring's time early flushes may take (_flush_early): one is
#: made no sooner than its executor's mean launch service divided by this
#: after the one before
_EARLY_SHARE = 0.5
#: FlushTrigger (wf_native.cpp): what cut a launch, on its launch record
_TRIGGERS = ("natural", "early", "forced", "eos", "barrier")

#: what wf_core_stream_stats writes, in its order (cumulative but for
#: ``rows_held`` and ``keys``, which are levels)
_STREAM_STATS = ("rows_out_of_order", "late_rows", "rows_reinserted",
                 "rows_held", "rows_held_peak", "watermark_fires",
                 "merges", "merge_ns", "fire_ns", "keys")

_U64 = (1 << 64) - 1


def _mix64(x: int) -> int:
    """splitmix64, bit-identical to wf_native.cpp's mix64 — the key→shard
    hash, needed host-side to route a migrated key's blob to the shard
    sub-core that will process its future rows."""
    x = (x + 0x9E3779B97F4A7C15) & _U64
    x = ((x ^ (x >> 30)) * 0xBF58476D1CE4E5B9) & _U64
    x = ((x ^ (x >> 27)) * 0x94D049BB133111EB) & _U64
    return x ^ (x >> 31)


class NativeStateSnapshot:
    """Checkpoint handle over the native core's exported state blobs
    (recovery layer, docs/ROBUSTNESS.md "Native state ABI").

    Unlike the resident ring's RingSnapshot, the C++ tables are MUTABLE —
    the byte copy must happen at the barrier (wf_core_state_export runs on
    the node thread, under the drained cut) — so resolve(), on the
    supervisor's writer thread, only packages the already-captured bytes
    into the pickle-ready dict.

    A blob is the bytes as the export wrote them: ``bytes``, or a view of
    one of its core's export buffers (``_export_buffer``), which the core
    writes again only once this handle and every other holder of the view
    are gone.  ``resolve()`` copies views out, so what is pickled never
    shares a buffer."""

    __slots__ = ("blobs", "abi")

    def __init__(self, blobs, abi: int):
        self.blobs = tuple(blobs)   # one blob per key shard
        self.abi = int(abi)

    @property
    def nbytes(self) -> int:
        return sum(len(b) for b in self.blobs)

    def resolve(self) -> dict:
        return {"kind": "native", "abi": self.abi,
                "blobs": tuple(bytes(b) for b in self.blobs)}


def _ship_loop(core_ref, ship_q, shard, shard_id):
    """Ship-thread main: one thread per key shard, so the shards'
    device_put / dispatch / harvest overlap (a single thread would
    serialize all shards' transfers).  With nothing to send and a launch in
    flight it waits on the oldest one and hands the result over when it
    lands, instead of sleeping until the next poke; a poke that arrives
    during the wait is served when the wait returns, the rest of one device
    step at most.  Resolves the core weakref per token or harvest and holds
    only the executor through a wait, so the thread never pins the core's
    lifetime (a dead core ends the loop)."""
    ex = None   # the executor whose oldest launch to wait on, if any
    while True:
        waited = ex is not None and ship_q.empty()
        if waited:
            try:
                turn = (ex.harvest_oldest(), None)
            except BaseException as e:  # surfaced on the node thread
                turn = (None, e)
        elif ex is not None:
            tok = ship_q.get()      # a poke came meanwhile: it goes first
        else:
            # nothing to launch, nothing in flight: the ship thread's
            # share of an idle chip
            with profile.span("ship_idle", shard=shard_id):
                tok = ship_q.get()
        if not waited and tok is None:
            return
        core = core_ref()
        if core is None:
            return
        ex = (core._ship_waited(shard, *turn) if waited
              else core._ship_token(tok, shard))
        del core


class NativeResidentCore:
    """Drop-in for ResidentWinSeqCore with the hot loop in C++.

    ``dense_positions`` (a Pane_Farm's window stage over its own pane
    stream, core/winseq.py) is taken and NOT acted on: the C++ triggerer
    (``wf_native.cpp``, role code 2) fires a count-based window on the
    first id at or past its end, the reference's rule, so such a stage's
    result leaves one pane later than from the Python cores — the same
    rows either way.  Only the Python delegate (``_fall_back``) is handed
    it on."""

    def __init__(self, spec: WindowSpec, reducer: Reducer,
                 batch_len: int = 8192, flush_rows: int = 1 << 20,
                 config: PatternConfig = None, role: Role = Role.SEQ,
                 map_indexes=(0, 1), result_ts_slide=None, device=None,
                 depth: int = 8, compute_dtype=None, shards: int = 1,
                 overlap: bool = True, worker_index: int = 0,
                 max_delay_ms=None, mesh=None, fire_on: str = "key",
                 holdback: int = 0, dense_positions: bool = False):
        from ..native import load
        from ..ops.functions import MultiReducer
        from ..ops.resident import make_executor
        from .win_seq_tpu import (_ARGEXT_PLACEMENT, _arg_parts,
                                  _argext_misplaced, _executor_family,
                                  _native_refusal, _native_stream_refusal,
                                  acc_dtypes_by_field,
                                  resolve_worker_device, split_pos_max)
        self._lib = load()
        if self._lib is None:
            raise RuntimeError("native library unavailable")
        if isinstance(reducer, MultiReducer):
            # counts come from window lengths and MAX over the position
            # field from the C++ archive's per-window last row (hpmax) —
            # e.g. YSB's COUNT + MAX(ts) + SUM(revenue) ships only revenue;
            # the other stats stage one int64 column per distinct field
            # (C++ kMaxFields = 4) into per-field device rings
            self._dev_parts, self._pos_max_parts = \
                split_pos_max(spec, reducer)
            self._count_parts = reducer.count_parts
        elif isinstance(reducer, (Reducer, ArgReducer)):
            self._dev_parts = [reducer]
            self._pos_max_parts = []
            self._count_parts = []
        else:
            raise TypeError("native resident core needs a builtin "
                            "(Multi)Reducer or ArgReducer")
        #: the arg-extremum stat, if the function has one: its ring is
        #: evaluated by the wf_step_argext family and its winning row read
        #: back from the C++ archive at harvest (_gather_payload)
        args = _arg_parts(self._dev_parts)
        if len(args) > 1:
            raise TypeError("one arg-extremum per window function (each "
                            f"names its own winning row): got {args}")
        self._arg = args[0] if args else None
        #: columns the C++ archive keeps beside the shipped ones and never
        #: ships: the tie-break id first, then what the result carries
        #: (``ts`` needs none: a time-based row's position is its ts, and a
        #: count-based arg-extremum core archives the column,
        #: wf_core_arg_gather)
        self._carry_cols = ()
        if self._arg is not None:
            if _argext_misplaced(mesh, shards):
                raise ValueError(
                    f"the arg-extremum family runs {_ARGEXT_PLACEMENT}")
            self._carry_cols = tuple(dict.fromkeys(
                (self._arg.id_field,) + tuple(
                    src for src, _d in self._arg.carry if src != "ts")))
        # the limits the router routes around (win_seq_tpu.plan_core)
        refusal = _native_refusal(self._dev_parts,
                                  int(self._lib.wf_max_fields()))
        if refusal is not None:
            raise TypeError(refusal)
        self._dev_part = self._dev_parts[0]
        self._ship_fields = tuple(dict.fromkeys(
            p.field for p in self._dev_parts))
        family = _executor_family("native", self._dev_parts)
        #: ``"stream"``: windows close on the stage's watermark -- the
        #: highest ``ts`` taken in less ``holdback`` -- and rows reach
        #: archive and ring once it has passed them (wf_core_set_stream)
        self.fire_on = fire_on
        self.holdback = int(holdback)
        if fire_on == "stream":
            refusal = _native_stream_refusal(
                spec, config, role, family, mesh is not None, shards,
                max_delay_ms, holdback)
            if refusal is not None:
                raise ValueError(refusal)
            #: what the node reports (docs/OBSERVABILITY.md), cumulative
            self.rows_out_of_order = self.late_rows = 0
            self.rows_reinserted = self.rows_held_peak = 0
            self.watermark_fires = 0
            self.keys_live = self.keys_live_peak = 0
            self._stream_seen = dict.fromkeys(_STREAM_STATS, 0)
            self._stream_buf = (ctypes.c_longlong * len(_STREAM_STATS))()
            #: launch id -> the last window its fire closed (ship thread
            #: writes, node thread pops at harvest)
            self._progress = {}
        #: beyond ``regular`` the C++ core stages per-field rectangles
        self._multi = family != "regular"
        self.spec = spec
        self.reducer = reducer
        self.field = self._dev_part.field
        self.out_field = self._dev_part.out_field
        self.config = config or PatternConfig.plain(spec.slide_len)
        self.role = role
        self.map_indexes = map_indexes
        self.result_ts_slide = (result_ts_slide if result_ts_slide is not None
                                else spec.slide_len)
        self.result_schema = Schema(**reducer.result_fields)
        self._result_dtype = self.result_schema.dtype()
        self._args = dict(batch_len=batch_len, flush_rows=flush_rows,
                          config=config, role=role, map_indexes=map_indexes,
                          result_ts_slide=result_ts_slide, device=device,
                          depth=depth, compute_dtype=compute_dtype,
                          worker_index=worker_index,
                          max_delay_ms=max_delay_ms, mesh=mesh,
                          dense_positions=dense_positions)
        # latency bound (checked per process() call, chunk cadence)
        self.max_delay_s = (None if max_delay_ms is None
                            else max_delay_ms / 1e3)
        self._last_flush_t = None
        #: ring dtype per shipped field
        self._acc_by_field = acc_dtypes_by_field(self._dev_parts,
                                                 compute_dtype, spec)
        # key-sharded multithreading: shard t owns keys with
        # mix64(key) %% S == t (a hash decorrelated from the farm routing
        # modulus — see wf_native.cpp), each with an independent sub-core,
        # device ring, and launch queue; one GIL-released MT call
        # processes a chunk on S pool threads.  Shard rings spread over the
        # visible chips (worker_index * S + t round-robin) so a sharded
        # core on a multi-chip host keeps each shard's archive on its own
        # device, like the farms' per-worker device ownership.  With a
        # mesh every shard's ring is itself sharded P(kf, None) over every
        # chip: a multicore host spreads the hot loop over its cores while
        # each shard's dispatches still serve all key groups in one SPMD
        # program.
        # cap at 256: the C++ MT path routes rows via a per-row shard-id
        # *byte* array (wf_native.cpp:wf_cores_process_mt), so ids beyond
        # u8 would alias and double-process rows
        self.shards = max(min(int(shards), 256), 1)
        stats = tuple((p.op, p.field) for p in self._dev_parts)
        self.executors = [
            make_executor(
                family, self._ship_fields, stats, self._acc_by_field,
                mesh=mesh, depth=depth,
                device=(None if mesh is not None else resolve_worker_device(
                    device, worker_index * self.shards + t)))
            for t in range(self.shards)]
        self.executor = self.executors[0]
        #: process-wide number of shard 0's ship thread: spans and launch
        #: records name a shard as _shard_base + t, so two farm workers'
        #: shards stay apart
        self._shard_base = int(worker_index) * self.shards
        #: id of the _process_rows call that last fed the cores — the
        #: `cause` on the launches shipped after it
        self._cause = None
        self._batch_len = int(batch_len)
        self._acc_wire = (
            3 if self._acc_by_field[self.field].itemsize >= 8 else 2)
        self._flush_rows = int(flush_rows)
        #: per shard, when its last early flush was made (_flush_early)
        self._early_t = [0.0] * self.shards
        self._new_handles()
        #: recovery/rescale support requires the state-ABI symbols in the
        #: loaded .so (stale-library detection: snapshots decline loudly,
        #: check/graph.py's WF215 warns, rescale validate() refuses)
        self.has_state_abi = bool(getattr(self._lib, "wf_has_state_abi",
                                          False))
        #: control-plane keyed migration (control/rescale.py) — an
        #: instance attr, not a class attr: it follows the loaded library
        #: (a stream-time core's held-back rows are not in the state ABI)
        self.keyed_migratable = self.has_state_abi and fire_on != "stream"
        #: dataflow metrics sink, mirrored by Supervisor.attach_all (the
        #: core itself has no dataflow reference)
        self._obs_metrics = None
        #: recovery-mode latch (process_batches and friends): pins
        #: deterministic launch boundaries — no reactive coalescing, no
        #: early flush — so a replayed run's per-launch emission regroups
        #: exactly like the original's
        self._recovery_mode = False
        #: buffers state_snapshot exports into (_export_buffer)
        self._export_bufs = []
        self._delegate = None
        self._offsets = None
        self._salvaged = []  # results drained during a raise, returned to
                             # a caller that catches and keeps going
        # overlap mode: a dedicated ship thread owns the executors —
        # device_put/dispatch/harvest run concurrently with the next
        # chunk's C++ bookkeeping (the C++ launch queue is mutex-guarded
        # for this producer/consumer split).  WF_NO_OVERLAP disables for
        # sweeps (a 1-core host pays GIL contention for the overlap).
        self._overlap = bool(overlap) and os.environ.get(
            "WF_NO_OVERLAP", "") in ("", "0")
        self._ship_exc = None
        #: wakes the node thread that drives this core (``set_waker``), so
        #: what a ship thread hands over between two chunks is emitted by
        #: ``collect`` then and not with the next chunk
        self._waker = None
        #: launches ``collect`` took, and their result rows
        self.result_wakes = 0
        self.result_wake_rows = 0
        #: launches allowed to pile up in the C++ queue before process()
        #: throttles — restores the backpressure the synchronous ship loop
        #: provided (each queued Launch holds a staged K*R block)
        self._max_pending = 2 * depth
        #: absolute merged-rectangle area guard (cells = K * bucket(R)):
        #: stops pathological padded rectangles (one hot key at huge
        #: flush_rows) from blowing host memory; must admit a full
        #: ladder-deep merge of benchmark-shaped launches (16x of a
        #: 2^19-row flush = 2^23 cells)
        self._coalesce_cells = (1 << 24) // max(len(self._ship_fields), 1)
        if self._overlap:
            self._start_ship_threads()

    def _new_handles(self):
        """(Re)create the per-shard C++ cores with the constructor's
        config — shared by __init__ and state_restore (restore imports
        into FRESH handles rather than scrubbing live ones)."""
        spec, cfg = self.spec, self.config
        self._hs = [self._lib.wf_core_new(
            int(spec.win_len), int(spec.slide_len),
            0 if spec.win_type is WinType.CB else 1, _ROLE_CODE[self.role],
            int(cfg.id_outer), int(cfg.n_outer), int(cfg.slide_outer),
            int(cfg.id_inner), int(cfg.n_inner), int(cfg.slide_inner),
            int(self.map_indexes[0]), int(self.map_indexes[1]),
            int(self.result_ts_slide), self._batch_len, self._flush_rows,
            self._acc_wire) for _ in range(self.shards)]
        if self._multi:
            # per-field widest wire dtype (ship_fields order): the C++
            # flush narrows each column independently against its ring
            mw = (ctypes.c_int * len(self._ship_fields))(*[
                3 if self._acc_by_field[f].itemsize >= 8 else 2
                for f in self._ship_fields])
            for h in self._hs:
                got = self._lib.wf_core_set_fields(
                    h, len(self._ship_fields), mw)
                if got != len(self._ship_fields):
                    # a short accept would leave the missing columns'
                    # rectangles uninitialized at take time — refuse
                    raise TypeError(
                        f"native core accepted {got} fields, "
                        f"need {len(self._ship_fields)}")
        if self._arg is not None:
            # ties are resolved on the archive column of the field the
            # extremum runs over, wherever it stands among the shipped ones;
            # a declared window sizes ring and archives up front
            for h in self._hs:
                got = self._lib.wf_core_set_arg(
                    h, len(self._carry_cols),
                    self._ship_fields.index(self._arg.field),
                    self._arg.window_rows or 0)
                if got != len(self._carry_cols):
                    raise TypeError(
                        f"native core archives {got} carried columns, the "
                        f"arg-extremum needs {self._carry_cols}")
        if self.fire_on == "stream":
            for h in self._hs:
                if not self._lib.wf_core_set_stream(h, self.holdback):
                    raise ValueError(
                        "the native core refused fire_on='stream' for "
                        f"{self.spec} with holdback {self.holdback}")
        self._harr = (ctypes.c_void_p * self.shards)(*self._hs)
        #: bytes a row takes in its key's C++ archive: 8 a column -- the
        #: position, each shipped field, each carried column, and ``ts`` on
        #: a count-based arg-extremum alone (docs/OBSERVABILITY.md)
        self.archive_row_bytes = int(
            self._lib.wf_core_archive_row_bytes(self._hs[0]))

    @property
    def fast_rows(self) -> int:
        """Rows the C++ bulk path took (``Core::process_fast``: key-periodic
        in-order chunks on a one-shard count-based sum)."""
        return sum(int(self._lib.wf_core_fast_rows(h)) for h in self._hs)

    def _start_ship_threads(self):
        # one ship thread per shard: each owns its executor, so the
        # shards' wire traffic overlaps; threads hold only a weakref
        # (a live ship thread must not keep the core and its C++ heap
        # + device rings alive)
        self._out_q = _queue.SimpleQueue()
        self._ship_qs = [_queue.SimpleQueue()
                         for _ in range(self.shards)]
        self._ship_threads = [
            threading.Thread(
                target=_ship_loop,
                args=(weakref.ref(self), self._ship_qs[t], t,
                      self._shard_base + t),
                daemon=True, name=f"wf-ship.{self._shard_base + t}")
            for t in range(self.shards)]
        for th in self._ship_threads:
            th.start()

    def _stop_worker(self):
        me = threading.current_thread()
        for t, th in enumerate(getattr(self, "_ship_threads", ()) or ()):
            if th is not None and th.is_alive():
                self._ship_qs[t].put(None)
                # (a core's last reference can be the one its own ship
                # thread holds over a token: that thread reads its None
                # when it gets back to its loop)
                if th is not me:
                    th.join(timeout=10)
        self._ship_threads = []

    def __del__(self):
        if getattr(self, "_overlap", False):
            self._stop_worker()
        for h in getattr(self, "_hs", None) or ():
            self._lib.wf_core_free(h)
        self._hs = []

    # ------------------------------------------------------------ ship thread

    def _ship_token(self, tok, shard):
        """Serve one poke or drain on a ship thread.  Returns what the
        thread waits on next (_wait_target)."""
        kind, ev = tok
        got, failed = (), False
        try:
            while self._ship_launch(shard, force=(kind == "drain")):
                pass
            got = (self.executors[shard].drain() if kind == "drain"
                   else self.executors[shard].poll())
            for item in got:
                self._out_q.put(item)
        except BaseException as e:  # surfaced on the node thread
            self._ship_exc, failed = e, True
        if ev is not None:
            ev.set()
        if (got or failed) and kind != "drain":
            # (under a drain the node thread itself stands waiting)
            self._wake_node()
        return None if failed else self._wait_target(shard)

    def _ship_waited(self, shard, harvested, exc):
        """Hand over what a ship thread's own wait harvested, or the
        failure it met, as under a token."""
        if exc is not None:
            self._ship_exc = exc
            self._wake_node()
            return None
        for item in harvested:
            self._out_q.put(item)
        self._wake_node()
        return self._wait_target(shard)

    def _wake_node(self):
        """After a hand-over or a failure, on a ship thread: an idle node
        thread comes for it now (``collect``), a busy one with its next
        ``process``."""
        wake = self._waker
        if wake is not None:
            wake()

    def set_waker(self, wake) -> bool:
        """Take ``wake``, a callable for any thread that gets the thread
        driving this core to call ``collect`` if it is idle (the node's
        own ``Node._wake``; it must not pin that node).  Refused, with
        False, where no ship thread hands results over, or where launches
        and emissions must not follow the clock: the synchronous path,
        recovery mode, a ``max_delay_ms`` core (it keeps its timer), the
        Python delegate."""
        if (not self._overlap or self._recovery_mode
                or self.max_delay_s is not None
                or self._delegate is not None):
            return False
        self._waker = wake
        return True

    def _wait_target(self, shard):
        """The shard's executor, if its ship thread should now wait on its
        oldest launch: one is in flight and nothing is queued to send.
        (Not after a failure: the next token tries that path again.)"""
        ex = self.executors[shard]
        if ex._inflight and not self._lib.wf_launch_pending(self._hs[shard]):
            return ex
        return None

    def _raise_ship_exc(self, drained):
        """Surface a ship-thread failure; results already drained are
        stashed and returned by the next successful call, so a caller that
        catches the error and keeps streaming does not lose windows.
        Clears the stored exception so it is raised once."""
        self._salvaged.extend(drained)
        exc, self._ship_exc = self._ship_exc, None
        raise exc

    def _drain_out_q(self, handed="svc"):
        """Everything the ship threads have handed over; `handed` says
        which call of the node thread takes it (``wake``: ``collect``;
        ``svc``: any other) on each launch's ``harvest_wait`` record."""
        items = []
        while True:
            try:
                items.append(self._out_q.get_nowait())
            except _queue.Empty:
                break
        if items and profile.ENABLED:
            now = time.perf_counter_ns()
            for meta, _res in items:
                profile.amend("harvest_wait", meta[6][0],
                              since_end=("out_q_ms", now), handed=handed)
        return items

    def _take_handed(self, handed="svc"):
        """Drain `_out_q` on the node thread: a ship-thread failure is
        raised here, once; what an earlier raise salvaged goes first."""
        drained = self._drain_out_q(handed)
        if self._ship_exc is not None:
            self._raise_ship_exc(drained)
        out, self._salvaged = self._salvaged + drained, []
        return out

    def collect(self) -> np.ndarray:
        """The results the ship threads handed over since the node thread
        last took any, for that thread between two ``process`` calls
        (``WinSeqNode.on_wake``).  ``process`` keeps its own drain:
        whichever comes first takes the items, the other finds nothing."""
        if not self._overlap or self._delegate is not None:
            return np.zeros(0, dtype=self._result_dtype)
        taken = self._take_handed("wake")
        out = self._harvest(taken)
        if taken:
            self.result_wakes += len(taken)
            self.result_wake_rows += len(out)
            profile.add("result_wakes", len(taken))
            profile.add("result_wake_rows", len(out))
        return out

    # ------------------------------------------------------------- delegate

    def _fall_back(self):
        """Switch to the pure-Python resident core (non-int64 payloads)."""
        if self._arg is not None:
            raise TypeError(
                "the arg-extremum runs on the native resident core only: "
                f"fields {self._ship_fields + self._carry_cols} must be "
                "int64 columns of the stream")
        if self.fire_on == "stream":
            raise TypeError(
                "fire_on='stream' runs on the native resident core only: "
                f"fields {self._ship_fields} must be int64 columns")
        from .win_seq_tpu import ResidentWinSeqCore
        self._delegate = ResidentWinSeqCore(self.spec, self.reducer,
                                            **self._args)
        if self._overlap:
            self._stop_worker()
        for h in self._hs:
            self._lib.wf_core_free(h)
        self._hs = []
        return self._delegate

    def _field_offsets(self, batch):
        if self._offsets is None:
            f = batch.dtype.fields
            cols = self._ship_fields + self._carry_cols
            if (any(fl not in f or f[fl][0] != np.int64 for fl in cols)
                    or batch.dtype[MARKER_FIELD] != np.bool_):
                return None
            self._offsets = (batch.dtype.itemsize, f["key"][1], f["id"][1],
                             f["ts"][1], f[MARKER_FIELD][1],
                             f[self._ship_fields[0]][1])
            #: payload-column offsets, ship_fields order (the _f ABI)
            self._voffs = np.array([f[fl][1] for fl in cols],
                                   dtype=np.int64)
        return self._offsets

    # ------------------------------------------------------------ streaming

    # -- recovery (docs/ROBUSTNESS.md "Native state ABI") ------------------

    def _obs_count(self, name, n=1):
        m = self._obs_metrics
        if m is not None:
            m.counter(name).inc(n)

    def _obs_hist(self, name, v):
        m = self._obs_metrics
        if m is not None:
            m.histogram(name).observe(v)

    def _require_state_abi(self, what: str):
        """Loud decline when the loaded .so predates the state ABI — the
        same degradation as before the ABI existed (check WF215 warns at
        build time about exactly this)."""
        if not self.has_state_abi:
            from ..runtime.node import SnapshotUnsupported
            raise SnapshotUnsupported(
                f"the loaded native library lacks the state ABI "
                f"(wf_core_state_export): {what} unsupported — rebuild "
                f"native/libwfnative.so (make -C native) or set "
                f"WF_NO_NATIVE_CORE=1 to run the Python resident core")

    def _enter_recovery_mode(self):
        """Pin deterministic launch boundaries for recovery-mode runs:
        reactive coalescing fuses queued launches by measured launch
        service and the early flush follows the ring's idleness — both
        wall-clock-driven, so a replayed run's launch boundaries (and
        with them the per-launch emission seqs) would diverge from the
        original's.  Natural flushes alone are count-triggered."""
        if self._recovery_mode:
            return
        self._recovery_mode = True
        self._waker = None
        if self._overlap:
            # ship threads drain into ONE completion-ordered queue, so a
            # multi-shard core's emission interleaving is wall-clock —
            # recovery runs ship synchronously in shard-major order
            # instead (deterministic, at the cost of the transfer overlap)
            self._stop_worker()
            self._salvaged.extend(self._drain_out_q())
            self._overlap = False

    def _drain_entries(self):
        """Ship every queued launch and block out in-flight results;
        returns the raw per-launch harvest entries."""
        if self._overlap:
            evs = [threading.Event() for _ in self._ship_qs]
            for q, ev in zip(self._ship_qs, evs):
                q.put(("drain", ev))
            for ev in evs:
                ev.wait()
            return self._take_handed()
        harvested = []
        for t in range(self.shards):
            while self._ship_launch(t, force=True):
                pass
            harvested.extend(self.executors[t].drain())
        return harvested

    def process_batches(self, batch):
        """Recovery-mode process(): same work, ONE output batch per
        completed launch, in launch order (the _AsyncLaunchRecovery
        contract, win_seq_tpu.py).  Unlike the single-executor resident
        core, the sharded native core has one launch FIFO per shard with
        wall-clock completion interleaving — so recovery mode drains all
        shards each call and emits entries in shard-major order, trading
        the transfer/compute overlap for deterministic emission
        boundaries."""
        if self._delegate is not None:
            return self._delegate.process_batches(batch)
        self._enter_recovery_mode()
        if len(batch) and self._field_offsets(batch) is None:
            return self._fall_back().process_batches(batch)
        self._process_rows(batch)
        return [self._harvest([e]) for e in self._drain_entries()]

    def flush_batches(self):
        if self._delegate is not None:
            return self._delegate.flush_batches()
        self._enter_recovery_mode()
        return [self._harvest([e]) for e in self._eos_and_drain()]

    def checkpoint_drain_batches(self):
        """Epoch-barrier drain (WinSeqNode.checkpoint_prepare): flush
        pending rows/windows into launches — NOT eos, unfired windows stay
        pending; padded to the last natural launch's shape, so a barrier a
        second meets no step executable of its own — and block out the
        in-flight results (they pre-date the snapshot cut and would
        otherwise be lost on restore).  Afterwards the C++ cores are
        drained, which is exactly the precondition wf_core_state_export
        checks."""
        if self._delegate is not None:
            return self._delegate.checkpoint_drain_batches()
        self._enter_recovery_mode()
        for h in self._hs:
            self._lib.wf_core_barrier_flush(h)
        return [self._harvest([e]) for e in self._drain_entries()]

    def _export_buffer(self, nbytes: int) -> np.ndarray:
        """A uint8 buffer of at least ``nbytes`` that nobody holds any more:
        one this core exported into before, whose snapshot (the views of it)
        has been dropped by everyone -- the recovery record replaces it at
        the next commit, the checkpoint writer once it is pickled -- else a
        new one of twice the size.  A snapshot a second is up to a whole
        window of archive columns; mapped afresh each time, its first touch
        of every page cost more than the copy (0.8 s a snapshot of 0.1-0.24
        GB on the benchmark's host, PERF.md PR 40), and twice with the copy
        into ``bytes`` that used to follow.  In steady state two buffers a
        shard alternate: the committed snapshot's and the one being
        written."""
        bufs, best = self._export_bufs, None
        for i in range(len(bufs)):
            # free: held by the list and by the call's argument alone
            if (sys.getrefcount(bufs[i]) == 2 and len(bufs[i]) >= nbytes
                    and (best is None or len(bufs[i]) < len(bufs[best]))):
                best = i
        if best is not None:
            return bufs[best]
        # outgrown buffers go as soon as nobody holds them
        self._export_bufs = [bufs[i] for i in range(len(bufs))
                             if sys.getrefcount(bufs[i]) > 2]
        buf = np.empty(max(2 * nbytes, 1 << 16), dtype=np.uint8)
        self._export_bufs.append(buf)
        return buf

    def state_snapshot(self):
        """Export the drained C++ state (per-key archives + window/
        ordering counters) into per-shard blobs.  Must run at a barrier
        after checkpoint_drain_batches — an undrained core refuses.
        Device ring contents never cross: restore zeroes the ring
        geometry and the next flush rebases from the imported archives,
        the native analog of the resident core's no-ring-snapshot path."""
        if self._delegate is not None:
            return {"kind": "native_delegate",
                    "inner": self._delegate.state_snapshot()}
        self._require_state_abi("epoch snapshots")
        if self.fire_on == "stream":
            from ..runtime.node import SnapshotUnsupported
            raise SnapshotUnsupported(
                "a stream-time native core's held-back rows are not in the "
                "native state ABI yet")
        if self.max_delay_s is not None:
            # wall-clock flushes make replay launch boundaries (and so
            # emission seqs) nondeterministic — same decline as the
            # Python resident core's
            from ..runtime.node import SnapshotUnsupported
            raise SnapshotUnsupported(
                "max_delay_ms wall-clock flushes make replay emission "
                "boundaries nondeterministic; recovery supports "
                "count-triggered flushes only")
        lib = self._lib
        blobs = []
        for h in self._hs:
            n = int(lib.wf_core_state_size(h))
            if n < 0:
                raise RuntimeError(
                    "native core not drained at the snapshot barrier "
                    "(checkpoint_prepare must flush + drain first)")
            buf = self._export_buffer(n)
            got = int(lib.wf_core_state_export(h, buf.ctypes.data, n))
            if got != n:
                raise RuntimeError(
                    f"native state export wrote {got} of {n} bytes")
            blob = buf[:n]
            blob.flags.writeable = False
            blobs.append(blob)
        snap = NativeStateSnapshot(blobs, abi=int(lib.wf_abi_version()))
        self._obs_count("native_state_exports")
        self._obs_count("native_state_export_bytes", snap.nbytes)
        self._obs_hist("native_state_blob_bytes", snap.nbytes)
        return snap

    def state_restore(self, snap):
        if isinstance(snap, NativeStateSnapshot):
            # (the handle's own blobs: a restore copies nothing out)
            snap = {"kind": "native", "abi": snap.abi, "blobs": snap.blobs}
        kind = snap.get("kind")
        if kind == "native_delegate":
            if self._delegate is None:
                self._fall_back()
            self._delegate.state_restore(snap["inner"])
            return
        if kind != "native":
            raise RuntimeError(
                f"NativeResidentCore cannot restore snapshot kind {kind!r}")
        self._require_state_abi("state restore")
        blobs = snap["blobs"]
        if len(blobs) != self.shards:
            raise RuntimeError(
                f"snapshot has {len(blobs)} shard blobs, core has "
                f"{self.shards} shards")
        # ship threads reach the C++ handles through queued tokens: join
        # them BEFORE freeing (use-after-free otherwise), rebuild after
        if self._overlap:
            self._stop_worker()
        for h in self._hs:
            self._lib.wf_core_free(h)
        self._hs = []
        self._new_handles()
        nbytes = 0
        for h, blob in zip(self._hs, blobs):
            buf = np.frombuffer(blob, dtype=np.uint8)
            rc = int(self._lib.wf_core_state_import(
                h, buf.ctypes.data, len(blob)))
            if rc != 0:
                raise RuntimeError(
                    f"native state import failed (code {rc})")
            nbytes += len(blob)
        # executors: drop in-flight work and rings from the crashed run;
        # the imported cores rebase at their next flush, re-shipping
        # every live row
        for ex in self.executors:
            inv = getattr(ex, "invalidate", None)
            if inv is not None:
                inv()
            else:
                ex._inflight.clear()
                ex._ready = []
        self._salvaged = []
        self._ship_exc = None
        self._last_flush_t = None
        if self._overlap:
            self._start_ship_threads()
        self._obs_count("native_state_imports")
        self._obs_count("native_state_import_bytes", nbytes)

    # -- control-plane keyed migration (control/rescale.py) ---------------

    def _shard_of(self, key: int) -> int:
        return int(_mix64(key & _U64) % self.shards) if self.shards > 1 \
            else 0

    def keyed_state_keys(self):
        """Keys with live native state, across all shards (sorted for a
        deterministic migration selection)."""
        self._require_state_abi("keyed-state migration")
        if self._delegate is not None:
            raise RuntimeError(
                "native core fell back to the Python delegate mid-stream; "
                "keyed migration state is no longer in the C++ tables")
        from ..native import p_i64
        lib = self._lib
        parts = []
        for h in self._hs:
            n = int(lib.wf_core_key_count(h))
            if n == 0:
                continue
            arr = np.empty(n, dtype=np.int64)
            got = int(lib.wf_core_key_list(
                h, arr.ctypes.data_as(p_i64), n))
            parts.append(arr[:min(got, n)])
        if not parts:
            return np.zeros(0, dtype=np.int64)
        return np.sort(np.concatenate(parts))

    def keyed_state_export(self, keys):
        """Export-and-neutralize the given keys (move semantics, like
        WinSeqCore's pop): the old owner never emits their windows again;
        the blobs re-import on the new owner inside the same barrier."""
        self._require_state_abi("keyed-state migration")
        lib = self._lib
        blobs = {}
        for k in np.asarray(keys, dtype=np.int64).tolist():
            k = int(k)
            for h in self._hs:
                n = int(lib.wf_core_key_state_size(h, k))
                if n != -2:     # -2 = key not on this shard
                    break
            if n < 0:
                raise RuntimeError(
                    f"native keyed export refused for key {k} "
                    f"(code {n}: core not drained or key unknown)")
            buf = np.empty(max(n, 1), dtype=np.uint8)
            got = int(lib.wf_core_key_export(h, k, buf.ctypes.data, n))
            if got != n:
                raise RuntimeError(
                    f"native keyed export wrote {got} of {n} bytes "
                    f"for key {k}")
            rc = int(lib.wf_core_key_neutralize(h, k))
            if rc != 0:
                raise RuntimeError(
                    f"native key neutralize failed for key {k} "
                    f"(code {rc})")
            blobs[k] = buf[:n].tobytes()
        nbytes = sum(len(b) for b in blobs.values())
        self._obs_count("native_state_exports")
        self._obs_count("native_state_export_bytes", nbytes)
        self._obs_hist("native_state_blob_bytes", nbytes)
        return {"kind": "native_keys",
                "abi": int(lib.wf_abi_version()), "blobs": blobs}

    def keyed_state_import(self, frag):
        self._require_state_abi("keyed-state migration")
        kind = frag.get("kind")
        if kind != "native_keys":
            raise TypeError(
                f"native core cannot import fragment kind {kind!r}")
        lib = self._lib
        nbytes = 0
        for k, blob in frag["blobs"].items():
            buf = np.frombuffer(blob, dtype=np.uint8)
            rc = int(lib.wf_core_key_import(
                self._hs[self._shard_of(int(k))],
                buf.ctypes.data, len(blob)))
            if rc != 0:
                raise RuntimeError(
                    f"native keyed import failed for key {k} (code {rc})")
            nbytes += len(blob)
        self._obs_count("native_state_imports")
        self._obs_count("native_state_import_bytes", nbytes)

    def process(self, batch: np.ndarray) -> np.ndarray:
        if self._delegate is not None:
            return self._delegate.process(batch)
        if len(batch) == 0 and self.max_delay_s is None:
            # keepalive harvesting only matters under a latency bound
            return np.zeros(0, dtype=self._result_dtype)
        if len(batch) and self._field_offsets(batch) is None:
            return self._fall_back().process(batch)
        self._process_rows(batch)
        if (len(batch) and batch[MARKER_FIELD][-1]
                and not self._recovery_mode):
            # a progress row closes the batch (core/tuples.progress_row):
            # the stage before says nothing more comes for the windows it
            # fired, and nothing may come at all for a slide.  The windows
            # the row closed here are cut into a launch now and their
            # results leave with this call, not with the next batch's
            profile.add("progress_seen")
            for h in self._hs:
                if self._lib.wf_core_fired_pending(h):
                    self._lib.wf_core_force_flush(h)
            return self._harvest(self._drain_entries())
        if self._overlap:
            return self._harvest(self._take_handed())
        harvested = []
        for t in range(self.shards):
            while self._ship_launch(t):
                pass
            harvested.extend(self.executors[t].poll())
        return self._harvest(harvested)

    def _process_rows(self, batch):
        """Feed one chunk through the C++ bookkeeping (flush cadence,
        ship-thread pokes + backpressure included);
        harvest collection is the caller's (process vs process_batches)."""
        b = np.ascontiguousarray(batch) if len(batch) else None
        launched = 0
        if b is not None:
            itemsize, o_key, o_id, o_ts, o_mk, o_val = self._offsets
            cause = self._cause = profile.next_id()
            with profile.span("native_bookkeeping", cause=cause):
                if self._multi:
                    from ..native import p_i64
                    launched = self._lib.wf_cores_process_mt_f(
                        self._harr, self.shards, b.ctypes.data, len(b),
                        itemsize, o_key, o_id, o_ts, o_mk,
                        self._voffs.ctypes.data_as(p_i64))
                else:
                    launched = self._lib.wf_cores_process_mt(
                        self._harr, self.shards, b.ctypes.data, len(b),
                        itemsize, o_key, o_id, o_ts, o_mk, o_val)
        if self.max_delay_s is not None:
            now = time.monotonic()
            if self._last_flush_t is None or launched:
                # natural flushes restart the latency clock: a saturated
                # stream must not fragment launches at max_delay cadence
                self._last_flush_t = now
            elif now - self._last_flush_t >= self.max_delay_s:
                # ship pending windows/rows now (test_micro latency bound)
                for h in self._hs:
                    self._lib.wf_core_force_flush(h)
                self._last_flush_t = now
        if self.fire_on == "stream":
            self._sync_stream_stats()
        elif (b is not None and not launched and self.max_delay_s is None
                and not self._recovery_mode):
            self._flush_early()
        if self._overlap:
            for q in self._ship_qs:
                q.put(("ship", None))
            # backpressure: if the device path is slower than ingestion,
            # wait for the ship threads to work the C++ queues down
            # (re-poking them each beat: a ship thread that held a launch
            # for coalescing has no other wake-up once tokens stop)
            with profile.span("backpressure_wait", cause=self._cause):
                beats = 0
                while (self._ship_exc is None
                       and max(self._lib.wf_launch_pending(h)
                               for h in self._hs) > self._max_pending):
                    time.sleep(0.001)
                    beats += 1
                    if beats % 20 == 0:
                        for q in self._ship_qs:
                            q.put(("ship", None))

    def _sync_stream_stats(self):
        """What the C++ stream-time core (one shard: ``_native_stream_refusal``)
        counted since the last call, onto this core's cumulative attributes
        and ``utils/profile`` (the two spans are timed inside the C++ call,
        so they carry no annotation and no ring record)."""
        buf = self._stream_buf
        self._lib.wf_core_stream_stats(self._hs[0], buf)
        tot = dict(zip(_STREAM_STATS, buf))
        new = {name: tot[name] - self._stream_seen[name] for name in tot}
        self._stream_seen = tot
        for name in ("rows_out_of_order", "late_rows", "rows_reinserted",
                     "watermark_fires"):
            setattr(self, name, tot[name])
            if new[name]:
                profile.add(name, new[name])
        self.rows_held_peak = tot["rows_held_peak"]
        self.keys_live = tot["keys"]
        self.keys_live_peak = max(self.keys_live_peak, tot["keys"])
        if new["merges"]:
            profile.record("reorder_merge", new["merge_ns"] / 1e9,
                           new["merges"])
        if new["watermark_fires"]:
            profile.record("watermark_fire", new["fire_ns"] / 1e9,
                           new["watermark_fires"])

    def _flush_early(self):
        """Device-following flush: `flush_rows` and `batch_len` are the
        upper bounds of a launch; below them a shard ships what it holds
        when its C++ core holds a fired window no launch carries yet, the
        call made no natural launch, and its ring is idle — nothing queued
        for the ship thread, nothing unserved on the device.  The executor's
        own measured service keeps such launches to `_EARLY_SHARE` of the
        ring's time, so a slow step is not fired at every small batch.
        Never in recovery mode (replayed launch boundaries may not depend
        on the clock) nor under `max_delay_ms` (that path keeps its
        timer).  The C++ side (`Core::flush`) pads an early launch to the
        last natural one's shape — no step executable of its own — and
        declines where it would have to rebase the ring."""
        lib = self._lib
        now = time.monotonic()
        rows = ctypes.c_longlong()
        for t, h in enumerate(self._hs):
            ex = self.executors[t]
            if (not lib.wf_core_fired_pending(h)
                    or lib.wf_launch_pending(h) or not ex.ring_idle()
                    or (now - self._early_t[t]) * _EARLY_SHARE
                    < ex.mean_service_s()):
                continue
            if lib.wf_core_flush_early(h, ctypes.byref(rows)):
                self._early_t[t] = now
                profile.add("flush_early")
                profile.add("flush_early_rows", rows.value)

    def _eos_and_drain(self):
        """EOS every shard core, then ship + drain everything; returns
        the raw per-launch harvest entries (flush/flush_batches share
        this tail)."""
        for h in self._hs:
            self._lib.wf_core_eos(h)
        if self.fire_on == "stream":
            self._sync_stream_stats()
        return self._drain_entries()

    def flush(self) -> np.ndarray:
        if self._delegate is not None:
            return self._delegate.flush()
        out = self._harvest(self._eos_and_drain())
        # the stream is over and harvested: the archives go now, not when
        # the graph's objects are collected
        for h in self._hs:
            self._lib.wf_core_release(h)
        return out

    def use_incremental(self):
        raise TypeError("the device path is non-incremental only "
                        "(win_seq_gpu.hpp supports NIC device functors)")

    # ------------------------------------------------------- launch plumbing

    def _ship_launch(self, shard: int = 0, force: bool = False) -> bool:
        lib = self._lib
        handle = self._hs[shard]
        ex = self.executors[shard]
        pending = lib.wf_launch_pending(handle)
        if pending == 0:
            return False
        # recovery mode never coalesces: merged launches would make the
        # per-launch emission boundaries wall-clock-dependent (replay
        # would regroup differently and break the per-edge seq dedup)
        # (nor does the arg-extremum family: nothing prewarms its merged
        # shapes, so a merge would compile cold in mid-run)
        coalesce = not self._recovery_mode and self._arg is None
        if (coalesce and not force and pending <= self._max_pending
                and self.max_delay_s is None):
            # (beyond _max_pending the hold is skipped: the producer's
            # backpressure loop waits on this queue, so holding there
            # would livelock — and the memory bound outranks RTT savings.
            # A latency-bounded core never holds: a launch parked behind
            # a stalled device would blow the max_delay budget by design.)
            if ex.unready_count() >= _DISPATCH_WINDOW:
                # launches saturated: hold this one so the queue deepens and
                # the next ship fuses the backlog into one dispatch
                return False
        if coalesce and pending > 1:
            # merge depth follows measured launch service: each dispatch
            # costs one service, so when launches take >20 ms to come
            # back the buddy ladder is allowed deeper ({1x,2x,4x} -> up to
            # 16x), cutting a backlogged run's dispatch count ~4x further.
            # Shapes stay on the power-of-2 ladder either way; benchmarks
            # pre-compile the deep buckets via prewarm_regular_ladder().
            svc = ex.mean_service_s()
            max_mult = 16 if svc >= 0.05 else (8 if svc >= 0.02 else 4)
            with profile.span("launch_coalesce",
                              shard=self._shard_base + shard):
                merged = lib.wf_launch_coalesce(
                    handle, self._coalesce_cells, 16, max_mult)
            if merged:
                from ..ops.resident import stats_add
                stats_add("merges", merged)
        K = ctypes.c_longlong()
        R = ctypes.c_longlong()
        B = ctypes.c_longlong()
        KP = ctypes.c_longlong()
        cap = ctypes.c_longlong()
        wire = ctypes.c_int()
        rebase = ctypes.c_int()
        if not lib.wf_launch_peek(handle, ctypes.byref(K), ctypes.byref(R),
                                  ctypes.byref(B), ctypes.byref(wire),
                                  ctypes.byref(rebase), ctypes.byref(KP),
                                  ctypes.byref(cap)):
            return False
        K, R, B = K.value, R.value, B.value
        # the launch's identity from here to _harvest: a process-wide id,
        # its ship thread, and the bookkeeping call that last fed the core
        tag = (profile.next_id(), self._shard_base + shard, self._cause)
        # allocate the device-ready zero-padded rectangle(s) and let the
        # C++ take fill them directly (no _pad2 re-copy on this thread)
        from ..ops.device import _bucket
        # what cut the launch, and the width the core reserved ring room
        # for where that is more than the rows' own bucket
        trigger = ctypes.c_int()
        rb = ctypes.c_longlong()
        lib.wf_launch_peek_cut(handle, ctypes.byref(trigger),
                               ctypes.byref(rb))
        KPp, Rb = KP.value, max(_bucket(max(R, 1)), rb.value)
        if self.fire_on == "stream":
            wid = ctypes.c_longlong()
            if lib.wf_launch_peek_progress(handle, ctypes.byref(wid)):
                self._progress[tag[0]] = wid.value
        habs = shifts = None
        if self._arg is not None:
            # each window's absolute first row, and the rows' slide when
            # the ring compacts
            habs = np.zeros(max(B, 1), dtype=np.int64)
            shifts = np.zeros(max(K, 1), dtype=np.int64)
            lib.wf_launch_peek_arg(handle,
                                   habs.ctypes.data_as(
                                       ctypes.POINTER(ctypes.c_longlong)),
                                   shifts.ctypes.data_as(
                                       ctypes.POINTER(ctypes.c_longlong)))
        blks = blk = None
        if self._multi:
            # one rectangle per ship field, each in the per-field wire
            # dtype the C++ flush narrowed that column to
            wires = (ctypes.c_int * len(self._ship_fields))()
            lib.wf_launch_peek_wires(handle, wires)
            blks = {f: np.empty((KPp, Rb), dtype=_WIRE_DTYPES[wires[i]])
                    for i, f in enumerate(self._ship_fields)}
        else:
            blk = np.empty((KPp, Rb), dtype=_WIRE_DTYPES[wire.value])
        offs = np.empty(K, dtype=np.int64)
        wrows = np.empty(max(B, 1), dtype=np.int32)
        hkey = np.empty(max(B, 1), dtype=np.int64)
        hid = np.empty(max(B, 1), dtype=np.int64)
        hts = np.empty(max(B, 1), dtype=np.int64)
        hlen = np.empty(max(B, 1), dtype=np.int64)
        hpm = (np.empty(max(B, 1), dtype=np.int64)
               if any(p.op == "max" for p in self._pos_max_parts)
               else None)
        hpmn = (np.empty(max(B, 1), dtype=np.int64)
                if any(p.op == "min" for p in self._pos_max_parts)
                else None)
        p32 = ctypes.POINTER(ctypes.c_int32)
        p64 = ctypes.POINTER(ctypes.c_longlong)
        regular = False
        cmax = ctypes.c_longlong()
        if (not self._multi and self._dev_part.op == "sum"
                and lib.wf_launch_peek_regular(handle, ctypes.byref(cmax))):
            regular = True
            rcount = np.empty(K, dtype=np.int32)
            rstart0 = np.empty(K, dtype=np.int32)
            rlen = np.empty(K, dtype=np.int32)
            widx = np.empty(max(B, 1), dtype=np.int32)
            lib.wf_launch_take_regular(
                handle, rcount.ctypes.data_as(p32),
                rstart0.ctypes.data_as(p32), rlen.ctypes.data_as(p32),
                widx.ctypes.data_as(p32))
        if regular:
            wstarts = wlens = None   # unread: skip the B*4-byte copies
            wstarts_p = wlens_p = None
        else:
            wstarts = np.empty(max(B, 1), dtype=np.int32)
            wlens = np.empty(max(B, 1), dtype=np.int32)
            wstarts_p = wstarts.ctypes.data_as(p32)
            wlens_p = wlens.ctypes.data_as(p32)
        with profile.span("launch_take", *tag) as sp:
            if profile.ENABLED:
                # the rows the C++ core holds for this rectangle, before
                # it pads them to (KPp, Rb): rows_shipped counts the padding
                live = (lib.wf_launch_live_rows(handle)
                        * len(self._ship_fields))
                profile.add("rows_live", live)
                sp.extra = {"rows_live": live,
                            "rows_shipped": KPp * Rb * len(self._ship_fields),
                            "trigger": _TRIGGERS[trigger.value]}
            if self._multi:
                ptrs = (ctypes.c_void_p * len(self._ship_fields))(
                    *[b.ctypes.data for b in blks.values()])
                lib.wf_launch_take_padded_f(
                    handle, ptrs, KPp, Rb,
                    offs.ctypes.data_as(p64), wrows.ctypes.data_as(p32),
                    wstarts_p, wlens_p,
                    hkey.ctypes.data_as(p64), hid.ctypes.data_as(p64),
                    hts.ctypes.data_as(p64), hlen.ctypes.data_as(p64),
                    hpm.ctypes.data_as(p64) if hpm is not None else None,
                    hpmn.ctypes.data_as(p64) if hpmn is not None else None)
            else:
                lib.wf_launch_take_padded(
                    handle, blk.ctypes.data_as(ctypes.c_void_p), KPp, Rb,
                    offs.ctypes.data_as(p64), wrows.ctypes.data_as(p32),
                    wstarts_p, wlens_p,
                    hkey.ctypes.data_as(p64), hid.ctypes.data_as(p64),
                    hts.ctypes.data_as(p64), hlen.ctypes.data_as(p64),
                    hpm.ctypes.data_as(p64) if hpm is not None else None,
                    hpmn.ctypes.data_as(p64) if hpmn is not None else None)
        if rebase.value == 1:
            ex.reset(max(K, 1), cap.value)
        elif cap.value > ex.cap:
            ex.grow(cap.value)      # arg-extremum rings grow on the device
        if getattr(ex, "mesh", None) is not None:
            # the mesh executors re-scatter rows onto their own (shard-
            # rounded) KP; hand them the live rows only, not the C++
            # padding
            if blk is not None:
                blk = blk[:K]
            if blks is not None:
                blks = {f: b[:K] for f, b in blks.items()}
        meta = (hkey[:B], hid[:B], hts[:B], hlen[:B],
                hpm[:B] if hpm is not None else None,
                hpmn[:B] if hpmn is not None else None, tag)
        if self._arg is not None:
            ex.launch(meta + (habs[:B],), blks, offs, wrows[:B],
                      wstarts[:B], wlens[:B],
                      shifts=shifts[:K] if rebase.value == 2 else None,
                      tag=tag)
        elif self._multi:
            ex.launch(meta, blks, offs, wrows[:B], wstarts[:B], wlens[:B],
                      tag=tag)
        elif regular:
            # per-key arithmetic descriptors instead of 3x B int32 arrays
            ex.launch_regular(meta, blk, offs, rcount, rstart0, rlen,
                              self.spec.slide_len, wrows[:B], widx[:B],
                              cmax=cmax.value, tag=tag)
        else:
            ex.launch(meta, blk, offs, wrows[:B], wstarts[:B], wlens[:B],
                      tag=tag)
        return True

    def _gather_payload(self, res, meta, arrs):
        """Fill the arg-extremum's result fields of one harvested launch:
        the extremum from the device, the winning row's fields from the
        C++ archive at the index the device found (node thread: the
        archives are its own).  Ties go to the lowest id there."""
        part = self._arg
        hkey, _hid, _hts, hlen, _pm, _pmn, tag, habs = meta
        ext, first, nties = arrs
        B = len(hkey)
        vals = ext.astype(part.dtype)
        empty = hlen == 0
        if empty.any():
            vals[empty] = part._identity()
        res[part.out_field] = vals
        if not B:
            return
        p64 = ctypes.POINTER(ctypes.c_longlong)
        p32 = ctypes.POINTER(ctypes.c_int32)
        out_ts = np.zeros(max(B, 1), dtype=np.int64)
        cols = np.zeros((max(len(self._carry_cols), 1), max(B, 1)),
                        dtype=np.int64)
        with profile.span("payload_gather", *tag):
            ext64 = np.ascontiguousarray(ext, dtype=np.int64)
            first = np.ascontiguousarray(first, dtype=np.int32)
            nties = np.ascontiguousarray(nties, dtype=np.int32)
            hkey, habs, hlen = (np.ascontiguousarray(a, dtype=np.int64)
                                for a in (hkey, habs, hlen))
            tied = self._lib.wf_core_arg_gather(
                self._hs[tag[1] - self._shard_base], B,
                hkey.ctypes.data_as(p64), habs.ctypes.data_as(p64),
                hlen.ctypes.data_as(p64), ext64.ctypes.data_as(p64),
                first.ctypes.data_as(p32), nties.ctypes.data_as(p32),
                out_ts.ctypes.data_as(p64), cols.ctypes.data_as(p64))
        if tied < 0:
            raise RuntimeError(
                "arg-extremum: a window's winning row was no longer in the "
                "native archive at harvest")
        if tied:
            profile.add("argext_ties", tied)
        col_of = {c: cols[i, :B] for i, c in enumerate(self._carry_cols)}
        col_of["ts"] = out_ts[:B]
        if part.id_out:
            res[part.id_out] = np.where(empty, NO_ARG_ID,
                                        col_of[part.id_field])
        for src, dst in part.carry:
            res[dst] = col_of[src]

    def _harvest(self, harvested) -> np.ndarray:
        if not harvested:
            return np.zeros(0, dtype=self._result_dtype)
        from .win_seq_tpu import finalize_window_values
        outs = []
        for meta, out in harvested:
            hkey, hid, hts, hlen, hpm, hpmn, tag = meta[:7]
            with profile.span("harvest_finalize", *tag):
                # multi executors return one array per stat (dev_parts
                # order; an arg-extremum three: extremum, first index,
                # count at the extremum); the single path returns the stat
                # array itself
                arrs = list(out if isinstance(out, tuple) else (out,))
                res = np.zeros(len(arrs[0]), dtype=self._result_dtype)
                res["key"] = hkey
                res["id"] = hid
                res["ts"] = hts
                for part in self._dev_parts:
                    if part is self._arg:
                        self._gather_payload(res, meta, arrs[:3])
                        del arrs[:3]
                    else:
                        res[part.out_field] = finalize_window_values(
                            part, arrs.pop(0), hlen)
                for part in self._count_parts:
                    res[part.out_field] = hlen.astype(part.dtype)
                for part in self._pos_max_parts:
                    res[part.out_field] = finalize_window_values(
                        part, hpm if part.op == "max" else hpmn, hlen)
            outs.append(res)
            if self.fire_on == "stream" and tag[0] in self._progress:
                # the launch ended a fire: what follows is at or past the
                # end of the last window it closed
                wid = self._progress.pop(tag[0])
                outs.append(progress_row(
                    self._result_dtype, wid,
                    wid * self.result_ts_slide + self.spec.win_len))
        return outs[0] if len(outs) == 1 else np.concatenate(outs)
