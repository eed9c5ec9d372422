"""Win_Seq_TPU: the sequential window core with device-batched evaluation —
the TPU graft of the reference's Win_Seq_GPU (win_seq_gpu.hpp).

Same window bookkeeping as the host core (it *is* the host core: one
subclass hook), but fired NIC windows are not evaluated inline: their
(start, len) ranges plus the staged archive slice are queued, and at
``batch_len`` fired windows one XLA computation evaluates them all.  Result headers (key, renumbered id, result ts) are
computed host-side at fire time, exactly like the reference pre-fills
``host_results[i].setInfo(...)`` before the kernel (win_seq_gpu.hpp:447-449).
Launches are asynchronous with bounded depth (vs the reference's per-batch
``cudaStreamSynchronize``, :481); results are emitted in launch order, so
per-key result order is preserved.

EOS leftovers run through the same device path padded to the smallest
bucket (the reference instead re-runs the functor on the CPU,
win_seq_gpu.hpp:533-581 — unnecessary here since the contract is a JAX
function, executable on any backend with identical semantics; that also
covers the reference's "host-callable device functor" testing trick).
"""

from __future__ import annotations

import queue
import threading
import weakref
from typing import NamedTuple, Optional

import numpy as np

from ..core.tuples import Schema
from ..core.windows import (PatternConfig, Role, WindowSpec, WinType,
                            check_dense_positions)
from ..core.winseq import WinSeqCore
from ..ops.device import (DeviceWindowExecutor, builtin_batch_fn,
                          cast_result)
from ..ops.functions import ArgReducer, MultiReducer, Reducer
from ..runtime.node import RuntimeContext
from ..utils import profile
from .basic import _Pattern
from .key_farm import KeyFarm
from .pane_farm import PaneFarm
from .win_farm import WinFarm
from .win_mapreduce import WinMapReduce
from .win_seq import WinSeqNode


def resolve_worker_device(device, i: int):
    """Per-worker device placement — farm worker *i* owns a chip the way
    each reference GPU worker owns a CUDA stream/device
    (win_farm_gpu.hpp:132-168, win_seq_gpu.hpp:271-306).

    ``None`` spreads workers round-robin over ``jax.devices()`` (on a
    single-chip host this degenerates to chip 0, unchanged); a list/tuple
    spreads over exactly those devices; a single device pins every worker
    to it."""
    if isinstance(device, (list, tuple)):
        return device[i % len(device)]
    if device is None:
        from ..ops.backend import default_devices
        devs = default_devices()
        return devs[i % len(devs)]
    return device


class JaxWindowFunction:
    """User window function for the device path: a JAX-traceable
    ``fn(keys, gwids, cols, mask) -> column(s)`` over a whole window batch
    — the TPU replacement for the reference's CUDA device functor
    ``F(key, gwid, data, res, size, scratch)`` (win_seq_gpu.hpp:54-67,
    deduced at meta_utils.hpp:173-180).

    A result may be a container (the reference's arbitrary ``result_t``):
    a result field of a sub-array dtype ``(base, (cap,))`` takes an output
    of ``(B, cap)``, ``cap`` slots a window, and ``count_field`` names the
    integer result field that says how many of them hold something.  A
    device function cannot raise, so that count is the container's TRUE
    size: the harvest raises on the host where it passes ``cap`` (the slots
    are then cut short), and no result of that launch leaves."""

    def __init__(self, fn, fields=("value",), result_fields=None,
                 field_dtypes=None, count_field=None, window_rows=None):
        self.fn = fn
        self.fields = tuple(fields)
        self.result_fields = dict(result_fields or {"value": np.int64})
        #: ring dtype per input field on the resident path (default int32;
        #: float columns need an explicit float32 here — the ring is typed
        #: at allocation, unlike the restaging path which stages whatever
        #: dtype each launch carries)
        self.field_dtypes = dict(field_dtypes or {})
        self.count_field = count_field
        #: what the caller knows of the stream, as ``ArgReducer.window_rows``
        #: is: the rows a window holds.  The function's step then keeps one
        #: padded length for every window up to it (ops/resident ``_pad_for``)
        #: instead of a shape a step of the ladder; no result depends on it
        if window_rows is not None and int(window_rows) <= 0:
            raise ValueError(f"window_rows must be positive: {window_rows}")
        self.window_rows = None if window_rows is None else int(window_rows)
        #: slots of a container-valued result (the narrowest sub-array
        #: result field's), None without a ``count_field``
        self.slot_cap = None
        if count_field is not None:
            widths = [np.dtype(dt).shape[0]
                      for dt in self.result_fields.values()
                      if np.dtype(dt).shape]
            if count_field not in self.result_fields or not widths:
                raise ValueError(
                    f"count_field={count_field!r} counts the slots of a "
                    "container-valued result: it must be a result field, "
                    "beside at least one of a sub-array dtype "
                    f"(got {self.result_fields})")
            self.slot_cap = int(min(widths))


def _init_slot_counters(core):
    """The counters :func:`_count_slots` keeps, on a core whose function's
    result is a container; None on every other (a node's log then leaves
    them out)."""
    fn = core._jax_fn
    counted = fn is not None and fn.count_field is not None
    core.pane_results = core.pane_points_kept = core.pane_overflow = (
        0 if counted else None)


def _count_slots(core, payload):
    """One harvested payload (``{result field: rows}``) of a container-valued
    result (``JaxWindowFunction(count_field=)``) onto its core's counters
    -- ``pane_results``, ``pane_points_kept`` (the slots that hold
    something), ``pane_overflow`` (results over the cap) -- and the raise
    where one passed its cap: the device cut its slots short, so none of
    the launch's results is handed on."""
    fn = core._jax_fn
    n, cap = payload[fn.count_field], fn.slot_cap
    over = int(np.count_nonzero(n > cap))
    kept = int(np.minimum(n, cap).sum())
    core.pane_results += len(n)
    core.pane_points_kept += kept
    core.pane_overflow += over
    profile.add("pane_results", len(n))
    profile.add("pane_points_kept", kept)
    if over:
        profile.add("pane_overflow", over)
        raise ValueError(
            f"a window function's result holds {int(n.max())} entries, over "
            f"the cap of {cap} slots its result fields have ({over} of "
            f"{len(n)} results of this launch): widen the sub-array result "
            "fields (cap); a truncated container is never handed on")


def _host_standin(winfunc):
    """Host-side function object carrying the result schema for the
    core/farm template plumbing (the device path never calls it)."""
    if isinstance(winfunc, (Reducer, MultiReducer, ArgReducer)):
        return winfunc
    if isinstance(winfunc, JaxWindowFunction):
        r = Reducer("count")
        r.result_fields = dict(winfunc.result_fields)
        return r
    raise TypeError(
        "the device path needs a builtin Reducer or a JaxWindowFunction "
        "(host Python functions cannot be staged to the TPU — same "
        "restriction as the reference's __device__ functor contract)")


class _AsyncLaunchRecovery:
    """Recovery-mode hooks shared by the async device cores
    (docs/ROBUSTNESS.md "Recovery").  Emission granularity is ONE batch
    per completed launch, in launch order: launch boundaries are
    count-triggered (deterministic), while how many launches any one
    poll()/drain() harvests is wall-clock — per-launch emission keeps a
    replayed run's output seq numbering identical to the original's
    regardless of harvest timing."""

    def _pre_poll(self):
        """Hook before harvesting in process_batches (the resident core
        runs its latency-bound flush here)."""

    def _per_launch(self, harvested):
        outs = []
        for entry in harvested:
            built = self._build_results([entry])
            if built:
                outs.append(built[0] if len(built) == 1
                            else np.concatenate(built))
        return outs

    def process_batches(self, batch):
        """Recovery-mode process(): same work, per-launch outputs."""
        WinSeqCore.process(self, batch)
        self._pre_poll()
        return self._per_launch(self.executor.poll())

    def flush_batches(self):
        WinSeqCore.flush(self)
        self._flush_batch()
        return self._per_launch(self.executor.drain())

    def checkpoint_drain_batches(self):
        """Epoch-barrier drain: launch the partial batch and block out
        the in-flight results (they pre-date the snapshot cut and would
        otherwise be lost on restore) — per launch, like every other
        recovery-mode emission."""
        self._flush_batch()
        return self._per_launch(self.executor.drain())


class DeviceWinSeqCore(_AsyncLaunchRecovery, WinSeqCore):
    """WinSeqCore whose fired-window evaluation is device-batched."""

    #: control-plane live rescale declined (docs/CONTROL.md): the
    #: inherited keyed hooks would migrate only the host bookkeeping
    #: while launch queues / staged device work stay behind
    keyed_migratable = False

    def __init__(self, spec: WindowSpec, winfunc, batch_len: int = 512,
                 config: PatternConfig = None, role: Role = Role.SEQ,
                 map_indexes=(0, 1), result_ts_slide=None, device=None,
                 depth: int = 4, compute_dtype=None,
                 dense_positions: bool = False):
        host_fn = _host_standin(winfunc)
        if isinstance(winfunc, Reducer):
            executor = DeviceWindowExecutor(
                builtin_batch_fn(winfunc.op, winfunc.field),
                fields=winfunc.required_fields,
                out_fields=tuple(winfunc.result_fields),
                device=device, depth=depth, compute_dtype=compute_dtype,
                out_dtypes=winfunc.result_fields,
                # empty windows must produce the host-path identity even
                # though device compute may run in a narrower dtype
                empty_fill={winfunc.out_field: winfunc._identity()})
            self._stage_fields = tuple(winfunc.required_fields)
        else:
            executor = DeviceWindowExecutor(
                winfunc.fn, fields=winfunc.fields,
                out_fields=tuple(winfunc.result_fields),
                device=device, depth=depth, compute_dtype=compute_dtype,
                out_dtypes=winfunc.result_fields)
            self._stage_fields = winfunc.fields
        super().__init__(spec, host_fn, config=config, role=role,
                         map_indexes=map_indexes,
                         result_ts_slide=result_ts_slide,
                         dense_positions=dense_positions)
        self.executor = executor
        self._jax_fn = winfunc if isinstance(winfunc, JaxWindowFunction) \
            else None
        _init_slot_counters(self)
        self.batch_len = batch_len
        # pending windows: list of (segment_cols, starts, lens) + headers
        self._segs = []        # [(cols{f: np}, starts, lens)]
        self._pending = 0
        self._hdr = []         # [(key, ids, ts) per enqueue]

    # -- device-batched NIC evaluation ------------------------------------

    def _emit_windows(self, key, st, lwids, eos: bool):
        spec = self.spec
        gwids = st.first_gwid + lwids * self.config.gwid_stride()
        ts = self._result_ts(st, lwids, gwids)
        ids = self._renumber_ids(key, st, gwids)
        starts_abs = spec.win_start(lwids) + st.initial_id
        ends_abs = spec.win_end(lwids) + st.initial_id
        p = st.archive.positions
        lo = np.searchsorted(p, starts_abs, side="left")
        hi = (np.full(len(lwids), len(p), dtype=np.int64) if eos
              else np.searchsorted(p, ends_abs, side="left"))
        base = int(lo[0]) if len(lo) else 0
        top = int(hi[-1]) if len(hi) else 0
        rows = st.archive.rows[base:top]
        cols = {f: rows[f].copy() for f in self._stage_fields}
        self._segs.append((cols, (lo - base).astype(np.int64),
                           (hi - lo).astype(np.int64),
                           np.full(len(lwids), key, dtype=np.int64), gwids))
        self._hdr.append((key, ids, ts))
        self._pending += len(lwids)
        if not eos and len(lwids):
            st.archive.purge_below(int(starts_abs[-1]))
        if self._pending >= self.batch_len:
            self._flush_batch()
        return None

    def _flush_batch(self):
        if not self._segs:
            return
        flat = {f: [] for f in self._stage_fields}
        starts, lens, keys, gwids = [], [], [], []
        off = 0
        for cols, s, l, k, g in self._segs:
            for f in self._stage_fields:
                flat[f].append(cols[f])
            starts.append(s + off)
            lens.append(l)
            keys.append(k)
            gwids.append(g)
            off += len(next(iter(cols.values()))) if cols else 0
        flat = {f: np.concatenate(v) if v else np.zeros(0, dtype=np.int64)
                for f, v in flat.items()}
        self.executor.launch(
            list(self._hdr), flat,
            np.concatenate(starts), np.concatenate(lens),
            np.concatenate(keys), np.concatenate(gwids))
        self._segs, self._hdr, self._pending = [], [], 0

    # -- harvest ----------------------------------------------------------

    def _build_results(self, harvested):
        outs = []
        for hdr, cols in harvested:
            off = 0
            for key, ids, ts in hdr:
                n = len(ids)
                payload = {f: v[off:off + n] for f, v in cols.items()}
                if self.pane_results is not None:
                    _count_slots(self, payload)
                outs.append(self._make_results(key, ids, ts, payload))
                off += n
        return outs

    def process(self, batch):
        super().process(batch)  # fired windows are enqueued, not returned
        outs = self._build_results(self.executor.poll())
        if not outs:
            return np.zeros(0, dtype=self._result_dtype)
        return np.concatenate(outs)

    def flush(self):
        super().flush()         # enqueue EOS leftovers
        self._flush_batch()     # launch the partial batch
        outs = self._build_results(self.executor.drain())
        if not outs:
            return np.zeros(0, dtype=self._result_dtype)
        return np.concatenate(outs)

    # -- recovery (docs/ROBUSTNESS.md): emission hooks come from
    # _AsyncLaunchRecovery ------------------------------------------------

    def state_snapshot(self):
        """Post-drain snapshot: the restaging executor keeps no state
        across launches, so only the host Win_Seq bookkeeping (per-key
        archives + counters) needs capturing."""
        import copy
        return {"_keys": copy.deepcopy(self._keys),
                "_in_dtype": self._in_dtype}

    def state_restore(self, snap):
        import copy
        self._keys = copy.deepcopy(snap["_keys"])
        self._in_dtype = snap["_in_dtype"]
        self._segs, self._hdr, self._pending = [], [], 0
        self.executor._inflight.clear()
        self.executor._ready = []

    def use_incremental(self):
        raise TypeError("the device path is non-incremental only "
                        "(win_seq_gpu.hpp supports NIC device functors)")


#: (op, result-dtype, acc-dtype) combinations already warned about —
#: resident cores are built per farm worker / per run, and repeating the
#: same narrowing warning for each of them is noise
_ACC_WARNED = set()


def _acc_range_safe(reducer: Reducer, acc: np.dtype, spec) -> bool:
    """True when the reducer's declared ``value_range`` proves its window
    results cannot exceed ``acc``'s range: min/max never leave the input
    range; a CB window's sum is bounded by win_len * max|value| (a TB
    window's row count is unbounded, so sums stay unprovable there)."""
    vr = getattr(reducer, "value_range", None)
    if vr is None or acc.kind == "f":
        return False
    m = max(abs(int(vr[0])), abs(int(vr[1])))
    if getattr(reducer, "base_op", reducer.op) in ("min", "max"):
        # (an arg-extremum's identity is the first integer outside the
        # range, ops/functions.ArgReducer: inside the bound too)
        bound = m
    elif (reducer.op == "sum" and spec is not None
          and spec.win_type is WinType.CB):
        bound = m * int(spec.win_len)
    else:
        return False
    info = np.iinfo(acc)
    return -bound >= info.min and bound <= info.max


def select_acc_dtype(reducer: Reducer, compute_dtype,
                     spec: WindowSpec = None) -> np.dtype:
    """Accumulate dtype for the resident device path: int32/float32 by
    default (TPU-native widths), overridable via ``compute_dtype``.  Warns
    when the reducer's result dtype exceeds the accumulate range — unless
    the reducer's declared ``value_range`` plus the window shape prove the
    results fit; raises if a 64-bit accumulate dtype is requested without
    jax x64 enabled (jax would silently canonicalize the buffers back down
    to 32-bit)."""
    if compute_dtype is not None:
        acc = np.dtype(compute_dtype)
    elif np.issubdtype(reducer.dtype, np.floating):
        acc = np.dtype(np.float32)
    else:
        acc = np.dtype(np.int32)
    if acc.itemsize >= 8:
        import jax
        if not jax.config.jax_enable_x64:
            raise ValueError(
                f"compute_dtype={acc} needs jax x64 enabled "
                "(jax.config.update('jax_enable_x64', True)); without it "
                "jax silently truncates device buffers to 32 bits")
    elif (reducer.dtype.itemsize > acc.itemsize
          and not _acc_range_safe(reducer, acc, spec)):
        key = (reducer.op, reducer.dtype.str, acc.str)
        if key not in _ACC_WARNED:
            _ACC_WARNED.add(key)
            import warnings
            warnings.warn(
                f"resident device path accumulates in {acc}; {reducer.op} "
                "results beyond its range will wrap — pass compute_dtype "
                "for wide ranges, or declare the field's value_range on "
                "the Reducer to prove the fit (warned once per "
                "configuration)",
                stacklevel=4)
    return acc


def acc_dtypes_by_field(parts, compute_dtype, spec: WindowSpec = None) -> dict:
    """Ring dtype per field for the resident cores: every stat picks its
    accumulate dtype (:func:`select_acc_dtype`), stats over one field share
    its ring at the widest of them, and must agree on the kind — a float
    ring would silently round sibling integer sums (float32 spacing > 1
    above 2^24)."""
    by_field = {}
    for p in parts:
        a = select_acc_dtype(p, compute_dtype, spec)
        prev = by_field.get(p.field)
        if prev is not None and prev.kind != a.kind:
            raise ValueError(
                f"stats over field {p.field!r} disagree on accumulate "
                f"kind ({prev} vs {a}): split the stats or pass an "
                "explicit compute_dtype")
        if prev is None or a.itemsize > prev.itemsize:
            by_field[p.field] = a
    return by_field


def finalize_window_values(reducer: Reducer, vals: np.ndarray,
                           lens: np.ndarray) -> np.ndarray:
    """Shared harvest step: cast device outputs to the reducer's result
    dtype and write the host identity over empty windows (min/max/prod
    identities exceed narrow accumulate dtypes; sum's identity 0 is what
    the cumsum difference already yields)."""
    owned = vals.dtype != reducer.dtype
    if owned:
        vals = vals.astype(reducer.dtype)
    if (reducer.op in ("min", "max", "prod") and len(lens)
            and (lens == 0).any()):
        if not owned:
            vals = vals.copy()
        vals[lens == 0] = reducer._identity()
    return vals


def _watch_loop(launches, wake):
    """A :class:`ResidentWinSeqCore`'s watcher thread: waits on each
    dispatched launch's device result in turn and wakes the node that
    drives the core when it is there.  It holds the queue, the waker and
    one launch's output arrays, and touches nothing else — harvest, fetch
    and every counter stay on the node's thread, so the executors keep
    their one-thread contract.  ``None`` ends it."""
    while True:
        item = launches.get()
        if item is None:
            return
        wait, out = item
        try:
            wait(out)
        except Exception:
            # a device failure is not raised here: the node's own poll or
            # fetch meets it, once, where it is raised without a watcher
            pass
        del item, wait, out     # (nothing of a launch held over the next get)
        wake()


class _ResultWatch:
    """The wake of a core that dispatches and harvests on its node's own
    thread: a watcher thread waits on each launch's device result and wakes
    the node, which takes the result in ``collect`` between two chunks.  The
    core has an ``executor`` and calls ``_init_watch`` once, ``_watch`` after
    a dispatch and ``_stop_watcher`` when every launch is in."""

    def _init_watch(self, worker_index: int):
        #: wakes the node thread that drives this core (``set_waker``), so
        #: a launch whose result lands between two chunks is taken by
        #: ``collect`` then and not with the next chunk
        self._waker = None
        #: the watcher thread and what ends it (``_watch``)
        self._watcher = None
        self._watch_q = None
        self._watch_stop = None
        self._watch_name = f"wf-watch.{worker_index}"
        #: launches ``collect`` took, and their result rows
        self.result_wakes = 0
        self.result_wake_rows = 0

    def set_waker(self, wake) -> bool:
        """Take ``wake``, a callable for any thread that gets the thread
        driving this core to call ``collect`` if it is idle (the node's
        own ``Node._wake``; it must not pin that node): the contract of
        ``NativeResidentCore.set_waker``.  Refused, with False, under
        ``max_delay_ms``: that core keeps its timer.  Two more never get
        here: the native core hands its Python delegate no waker, and the
        engine gives a node under ``recovery=`` no ``_wake`` to hand on."""
        # (a watcher of an earlier stream would call that stream's waker)
        self._stop_watcher()
        if getattr(self, "max_delay_s", None) is not None:
            return False
        self._waker = wake
        return True

    def _watch(self, out):
        """Hand a dispatched launch's device result to the watcher
        thread, which the first one starts."""
        if self._watcher is None:
            self._watch_q = q = queue.SimpleQueue()
            # the thread holds neither the core nor its executor: a core
            # that is dropped ends it, as the stream's end does
            self._watch_stop = weakref.finalize(self, q.put, None)
            self._watcher = threading.Thread(
                target=_watch_loop, args=(q, self._waker), daemon=True,
                name=self._watch_name)
            self._watcher.start()
        self._watch_q.put((self.executor.wait_ready, out))

    def _stop_watcher(self):
        th, self._watcher = self._watcher, None
        if th is not None:
            self._watch_stop()
            th.join(timeout=10)

    def _poll_woken(self) -> list:
        """The launches that became ready since the node thread last
        looked, harvested as ``handed`` by a wake."""
        ex = self.executor
        ex.handed = "wake"
        try:
            harvested = ex.poll()
        finally:
            ex.handed = "svc"
        return harvested

    def _count_wakes(self, launches: int, rows: int):
        if launches:
            self.result_wakes += launches
            self.result_wake_rows += rows
            profile.add("result_wakes", launches)
            profile.add("result_wake_rows", rows)


class ResidentWinSeqCore(_ResultWatch, _AsyncLaunchRecovery, WinSeqCore):
    """Window core whose archive lives in device HBM (ops/resident.py).

    Host-side it is the same Win_Seq bookkeeping as every other core; the
    differences from :class:`DeviceWinSeqCore` (which restages each fired
    window's rows per batch, like the reference's per-batch H2D memcpy,
    win_seq_gpu.hpp:451-476) are:

    * appended rows are mirrored once into the device ring archive, in the
      narrowest dtype holding their range — each row crosses the wire once;
    * fired windows are described by (ring row, start, len) only; append and
      evaluation fuse into one dispatch per flush;
    * the host archive's purge is deferred to flush time so a rebase (ring
      compaction) can always rebuild the ring from host-live rows.
    """

    #: control-plane live rescale declined (docs/CONTROL.md): a key's
    #: rows are mirrored into THIS worker's HBM ring archive — the
    #: inherited host-dict hooks cannot move that half (extending the
    #: migration to device rings rides ROADMAP Open item 5's ABI work)
    keyed_migratable = False

    def __init__(self, spec: WindowSpec, reducer, batch_len: int = 8192,
                 flush_rows: int = 1 << 20, config: PatternConfig = None,
                 role: Role = Role.SEQ, map_indexes=(0, 1),
                 result_ts_slide=None, device=None, depth: int = 8,
                 compute_dtype=None, worker_index: int = 0, mesh=None,
                 max_delay_ms=None, dense_positions: bool = False):
        from ..ops.resident import make_executor
        self._jax_fn = None
        self._pos_max_parts = []
        if isinstance(reducer, JaxWindowFunction):
            # arbitrary batched JAX window fn over device-resident rings —
            # one ring per input field (win_seq_gpu.hpp:54-67's arbitrary
            # functor over whole POD tuples, without per-fire restaging)
            self._device_parts = []
            self._count_parts = []
            self._jax_fn = reducer
        elif isinstance(reducer, MultiReducer):
            # multi-stat: every DEVICE-WORTHY stat evaluates over its
            # field's resident ring in one fused dispatch; counts come
            # free from window lengths, and MAX over the POSITION field
            # (ts for TB, id for CB) is free from the position-ordered
            # host archive (stream_archive.hpp ordering) — splitting it
            # out here means e.g. YSB's COUNT + MAX(ts) + SUM(revenue)
            # ships ONLY the revenue column (narrowed to int8 on the
            # wire), not ts
            self._device_parts, self._pos_max_parts = \
                split_pos_max(spec, reducer)
            self._count_parts = reducer.count_parts
            if not self._device_parts:
                # an entirely host-free aggregate forced onto the device
                # (use_resident=True, transfer benchmarking): ship the
                # position column after all — there is nothing else to
                # evaluate (make_core_for routes such aggregates to the
                # host core unless forced)
                self._device_parts, self._pos_max_parts = \
                    self._pos_max_parts, []
            if not self._device_parts:
                raise ValueError(
                    "resident MultiReducer needs >=1 non-count stat "
                    "(use Reducer('count') for pure counts)")
        elif isinstance(reducer, Reducer):
            self._device_parts = [reducer]
            self._count_parts = []
        else:
            raise TypeError("resident device path needs a builtin Reducer, "
                            "MultiReducer, or JaxWindowFunction")
        host_fn = _host_standin(reducer)
        super().__init__(spec, host_fn, config=config, role=role,
                         map_indexes=map_indexes,
                         result_ts_slide=result_ts_slide,
                         dense_positions=dense_positions)
        self.reducer = reducer
        family = _executor_family(
            "resident_py", None if self._jax_fn is not None
            else self._device_parts)
        self._ship_fields = (
            tuple(self._jax_fn.fields) if self._jax_fn is not None
            else tuple(dict.fromkeys(p.field for p in self._device_parts)))
        #: the one ring's field; None with a ring per field
        self.field = self._ship_fields[0] if family == "regular" else None
        # ring dtypes: reducer parts pick theirs (acc_dtypes_by_field);
        # fn-only fields use the fn's declared field_dtypes (default int32)
        acc_by_field = acc_dtypes_by_field(self._device_parts, compute_dtype,
                                           spec)
        if self._jax_fn is not None:
            declared = getattr(self._jax_fn, "field_dtypes", None) or {}
            for f in self._ship_fields:
                dt = np.dtype(declared.get(f, np.int32))
                if dt.itemsize >= 8:
                    # same guard select_acc_dtype applies: without x64
                    # jax silently canonicalizes the ring to 32 bits
                    import jax
                    if not jax.config.jax_enable_x64:
                        raise ValueError(
                            f"field_dtypes[{f!r}]={dt} needs jax x64 "
                            "enabled (jax.config.update("
                            "'jax_enable_x64', True))")
                acc_by_field.setdefault(f, dt)
        self.executor = make_executor(
            family, self._ship_fields,
            tuple((p.op, p.field) for p in self._device_parts),
            acc_by_field, jax_fn=self._jax_fn, mesh=mesh,
            device=(None if mesh is not None
                    else resolve_worker_device(device, worker_index)),
            depth=depth)
        self.batch_len = batch_len
        self.flush_rows = flush_rows
        # latency bound: ship pending windows/rows after this many ms even
        # when neither batch_len nor flush_rows is reached (checked per
        # process() call — the trigger cadence is the chunk cadence)
        self.max_delay_s = (None if max_delay_ms is None
                            else max_delay_ms / 1e3)
        self._last_flush_t = None
        self._rowmap = {}     # key -> dense ring row
        self._appended = {}   # key -> rows ever archived (abs row domain)
        self._launched = {}   # key -> rows already shipped to the ring
        self._base = {}       # key -> abs row index of ring column 0
        #: field -> key -> [column arrays not yet shipped]
        self._pend_cols = {f: {} for f in self._ship_fields}
        self._pend_rows = 0
        self._wdesc = []      # (key, abs_lo array, len array, gwids)
        self._hdr = []        # (key, ids, ts, lens) per fire
        self._n_wins = 0
        self._purge_pos = {}  # key -> purge threshold deferred to flush
        # this core harvests on the thread that drives it, so it says
        # itself which call took a launch (ops/resident ``handed``)
        self.executor.handed = "svc"
        self._init_watch(worker_index)
        _init_slot_counters(self)

    def collect(self) -> np.ndarray:
        """The results of the launches that became ready since the node
        thread last looked, for that thread between two ``process`` calls
        (``WinSeqNode.on_wake``).  ``process`` keeps its own poll:
        whichever comes first takes a launch, the other finds nothing."""
        harvested = self._poll_woken()
        out = self._concat(self._build_results(harvested))
        self._count_wakes(len(harvested), len(out))
        return out

    # ------------------------------------------------------------ bookkeeping

    def _on_append(self, key, st, rows):
        self._rowmap.setdefault(key, len(self._rowmap))
        for f in self._ship_fields:
            self._pend_cols[f].setdefault(key, []).append(
                np.asarray(rows[f]))
        self._appended[key] = self._appended.get(key, 0) + len(rows)
        self._pend_rows += len(rows)
        if self._pend_rows >= self.flush_rows:
            self._flush_batch()

    def _emit_windows(self, key, st, lwids, eos: bool):
        spec = self.spec
        self._rowmap.setdefault(key, len(self._rowmap))
        gwids = st.first_gwid + lwids * self.config.gwid_stride()
        ts = self._result_ts(st, lwids, gwids)
        ids = self._renumber_ids(key, st, gwids)
        starts_abs = spec.win_start(lwids) + st.initial_id
        ends_abs = spec.win_end(lwids) + st.initial_id
        p = st.archive.positions
        lo = np.searchsorted(p, starts_abs, side="left")
        hi = (np.full(len(lwids), len(p), dtype=np.int64) if eos
              else np.searchsorted(p, ends_abs, side="left"))
        live_start = self._appended.get(key, 0) - len(p)
        self._wdesc.append((key, lo + live_start, (hi - lo).astype(np.int64),
                            gwids))
        if self._pos_max_parts and len(p):
            # MAX/MIN over the position field, free from the ordered
            # archive: the window's last row holds the max and its first
            # row the min (empty windows fixed up to the identity at
            # harvest, finalize_window_values)
            pm = (p[np.minimum(np.maximum(hi - 1, 0), len(p) - 1)],
                  p[np.minimum(lo, len(p) - 1)])
        else:
            z = np.zeros(len(lwids), dtype=np.int64)
            pm = (z, z)
        self._hdr.append((key, ids, ts, (hi - lo).astype(np.int64), pm))
        self._n_wins += len(lwids)
        if not eos and len(lwids):
            # defer the purge so a flush-time rebase can rebuild the ring
            # from host-live rows (win_seq.hpp:390-392 purges at fire time)
            self._purge_pos[key] = max(self._purge_pos.get(key, -2 ** 62),
                                       int(starts_abs[-1]))
        if self._n_wins >= self.batch_len:
            self._flush_batch()
        return None

    # ------------------------------------------------------------------ flush

    def _flush_batch(self):
        if not self._wdesc and not self._pend_rows:
            return
        ex = self.executor
        # the host's part of the launch before the transfer, under the name
        # the native core's ship thread gives its own (OBSERVABILITY.md)
        with profile.span("launch_take"):
            blks, offs, wrows, wstarts, wlens = self._take_launch(ex)
        if self.field is None:
            # multi-field executor: ships every ring's rectangle + the
            # (keys, gwids) header columns the JAX fn contract receives
            if self._jax_fn is not None and self._wdesc:
                wkeys = np.concatenate([
                    np.full(len(lens), key, dtype=np.int64)
                    for key, _a, lens, _g in self._wdesc])
                wgwids = np.concatenate(
                    [g for _k, _a, _l, g in self._wdesc]).astype(np.int64)
            else:
                wkeys = wgwids = np.zeros(0, dtype=np.int64)
            ex.launch(self._hdr, blks, offs, wrows, wstarts, wlens,
                      wkeys=wkeys, wgwids=wgwids)
        else:
            ex.launch(self._hdr, blks[self.field], offs, wrows,
                      wstarts, wlens)
        if self._waker is not None:
            self._watch(ex._last_out)
        # --- advance cursors, apply deferred purges ---
        for key in self._rowmap:
            self._launched[key] = self._appended.get(key, 0)
        for key, pos in self._purge_pos.items():
            st = self._keys.get(key)
            if st is not None:
                st.archive.purge_below(pos)
        self._pend_cols = {f: {} for f in self._ship_fields}
        self._pend_rows = 0
        self._wdesc, self._hdr, self._n_wins = [], [], 0
        self._purge_pos = {}
        if self.max_delay_s is not None:
            # every flush (natural or forced) restarts the latency clock —
            # otherwise a saturated stream would fragment launches at
            # max_delay cadence despite fresh batch_len/flush_rows flushes
            import time as _time
            self._last_flush_t = _time.monotonic()

    def _take_launch(self, ex):
        """What the next launch ships: the per-field rectangles, the ring
        write offsets and the fired windows in ring coordinates -- after a
        rebase of the ring where it has no room for them."""
        from ..ops.resident import _bucket
        rowmap = self._rowmap
        K = len(rowmap)
        # --- decide append vs rebase ---
        # (KP < K, not KP < _bucket(K): the mesh executor's KP is a
        # multiple of its shard count rather than a power of two)
        rebase = ex.cap == 0 or ex.KP < max(K, 1)
        if not rebase:
            # the append rectangle is (K, Rb) with one global padded width,
            # so every key needs fill + Rb columns of room
            maxpend = max((self._appended.get(key, 0)
                           - self._launched.get(key, 0) for key in rowmap),
                          default=0)
            Rb = _bucket(max(maxpend, 1))
            for key in rowmap:
                fill = self._launched.get(key, 0) - self._base.get(key, 0)
                if fill + Rb > ex.cap:
                    rebase = True
                    break
        if rebase:
            counts = {}
            maxlive = 0
            for key in rowmap:
                st = self._keys.get(key)
                counts[key] = len(st.archive) if st is not None else 0
                maxlive = max(maxlive, counts[key])
            per_key_slack = max(self.flush_rows // max(K, 1), 64)
            ex.reset(K, _bucket(2 * maxlive + 2 * per_key_slack))
            R = maxlive
            srcs = {f: {key: ([np.asarray(self._keys[key].archive.rows[f])]
                              if key in self._keys else [])
                        for key in rowmap}
                    for f in self._ship_fields}
            for key in rowmap:
                self._base[key] = self._appended.get(key, 0) - counts[key]
                self._launched[key] = self._base[key]
            offs = np.zeros(ex.KP, dtype=np.int64)
        else:
            srcs = self._pend_cols
            counts = {key: self._appended.get(key, 0)
                      - self._launched.get(key, 0) for key in rowmap}
            R = max(counts.values(), default=0)
            offs = np.zeros(ex.KP, dtype=np.int64)
            for key, r in rowmap.items():
                offs[r] = self._launched.get(key, 0) - self._base.get(key, 0)
        # --- per-field rectangles in the narrowest wire dtype ---
        blks = {}
        for f in self._ship_fields:
            fsrcs = srcs[f]
            arrays = [a for key in rowmap for a in fsrcs.get(key, [])
                      if len(a)]
            if arrays:
                lo = min(a.min() for a in arrays)
                hi = max(a.max() for a in arrays)
                probe = np.array([lo, hi], dtype=arrays[0].dtype)
            else:
                probe = np.zeros(0, dtype=np.int64)
            wire = (ex.narrow_for(f, probe) if hasattr(ex, "narrow_for")
                    else ex.narrow(probe))
            blk = np.zeros((K, max(R, 1)), dtype=wire)
            for key, r in rowmap.items():
                c = 0
                for a in fsrcs.get(key, []):
                    blk[r, c:c + len(a)] = a
                    c += len(a)
            blks[f] = blk
        # --- window descriptors in ring coordinates ---
        if self._wdesc:
            wrows = np.concatenate([
                np.full(len(lens), rowmap[key], dtype=np.int64)
                for key, _, lens, _g in self._wdesc])
            wstarts = np.concatenate([
                abs_lo - self._base.get(key, 0)
                for key, abs_lo, _l, _g in self._wdesc])
            wlens = np.concatenate([lens for _k, _a, lens, _g in self._wdesc])
        else:
            wrows = wstarts = wlens = np.zeros(0, dtype=np.int64)
        return blks, offs[:K], wrows, wstarts, wlens

    # ---------------------------------------------------------------- harvest

    def _build_results(self, harvested):
        outs = []
        fn_fields = (tuple(self._jax_fn.result_fields.items())
                     if self._jax_fn is not None else ())
        for hdr, out in harvested:
            stat_arrs = out if isinstance(out, tuple) else (out,)
            off = 0
            for key, ids, ts, lens, pos_max in hdr:
                n = len(ids)
                payload = {}
                i = 0
                for p in self._device_parts:
                    payload[p.out_field] = finalize_window_values(
                        p, stat_arrs[i][off:off + n], lens)
                    i += 1
                for name, dt in fn_fields:
                    payload[name] = cast_result(stat_arrs[i][off:off + n], dt)
                    i += 1
                if self.pane_results is not None:
                    _count_slots(self, payload)
                for p in self._count_parts:
                    payload[p.out_field] = lens.astype(p.dtype)
                for p in self._pos_max_parts:
                    payload[p.out_field] = finalize_window_values(
                        p, pos_max[0] if p.op == "max" else pos_max[1],
                        lens)
                outs.append(self._make_results(key, ids, ts, payload))
                off += n
        return outs

    def _maybe_delay_flush(self):
        if self.max_delay_s is not None and (self._wdesc or self._pend_rows):
            import time as _time
            now = _time.monotonic()
            if self._last_flush_t is None:
                self._last_flush_t = now
            elif now - self._last_flush_t >= self.max_delay_s:
                self._flush_batch()
                self._last_flush_t = now

    def _concat(self, outs):
        if not outs:
            return np.zeros(0, dtype=self._result_dtype)
        return np.concatenate(outs)

    def process(self, batch):
        super().process(batch)  # fired windows are enqueued, not returned
        self._maybe_delay_flush()
        return self._concat(self._build_results(self.executor.poll()))

    def flush(self):
        super().flush()          # enqueue EOS leftovers
        self._flush_batch()      # launch the partial batch
        harvested = self.executor.drain()
        # every launch is in: the watcher has nothing left to wait for
        self._stop_watcher()
        return self._concat(self._build_results(harvested))

    # -- recovery (docs/ROBUSTNESS.md): emission hooks come from
    # _AsyncLaunchRecovery ------------------------------------------------

    def _pre_poll(self):
        self._maybe_delay_flush()

    #: include the HBM ring contents in snapshots (a functional-array
    #: handle whose device→host copy overlaps the next batches' compute,
    #: ops/resident.RingSnapshot); the Supervisor mirrors
    #: RecoveryPolicy.snapshot_rings here.  False = restore by forcing a
    #: rebase from the host-live archive rows instead.
    snapshot_rings = True
    #: ring/cursor bookkeeping captured alongside the host archives
    _RES_ATTRS = ("_rowmap", "_appended", "_launched", "_base")

    def state_snapshot(self):
        if self.max_delay_s is not None:
            # the latency-bound flush is wall-clock-triggered: replayed
            # LAUNCH boundaries would diverge from the original run's,
            # and with them the emission seqs — decline rather than
            # risk duplicated/lost windows after a restart
            from ..runtime.node import SnapshotUnsupported
            raise SnapshotUnsupported(
                "max_delay_ms wall-clock flushes make replay emission "
                "boundaries nondeterministic; recovery supports "
                "count-triggered flushes only")
        import copy
        snap = {
            "_keys": copy.deepcopy(self._keys),
            "_in_dtype": self._in_dtype,
            "resident": copy.deepcopy(
                {a: getattr(self, a) for a in self._RES_ATTRS}),
        }
        if self.snapshot_rings:
            snap["ring"] = self.executor.ring_snapshot()
        return snap

    def state_restore(self, snap):
        import copy
        self._keys = copy.deepcopy(snap["_keys"])
        self._in_dtype = snap["_in_dtype"]
        for a, v in snap["resident"].items():
            setattr(self, a, copy.deepcopy(v))
        self._pend_cols = {f: {} for f in self._ship_fields}
        self._pend_rows = 0
        self._wdesc, self._hdr, self._n_wins = [], [], 0
        self._purge_pos = {}
        self._last_flush_t = None
        ring = snap.get("ring")
        if ring is not None:
            self.executor.ring_restore(ring)
        else:
            # no ring in the snapshot: invalidate so the next flush
            # rebases — the deferred-purge invariant guarantees the
            # host archives still hold every ring-live row
            self.executor.invalidate()

    def use_incremental(self):
        raise TypeError("the device path is non-incremental only "
                        "(win_seq_gpu.hpp supports NIC device functors)")


#: reducer ops the resident path evaluates on device (count carries no
#: device work at all and routes to the HOST core via _host_free, as does
#: max over the position field; arbitrary JAX fns default to the
#: segment-restaging executor and opt into resident rings)
_RESIDENT_OPS = ("sum", "min", "max", "prod")


def split_pos_max(spec: WindowSpec, reducer: MultiReducer):
    """Partition a MultiReducer's non-count stats into (device_parts,
    pos_extremum_parts): MAX *and MIN* over the POSITION field (ts for
    TB, id for CB) are free from the position-ordered archive — the
    window's last row holds the max and its FIRST row the min — so
    neither ever ships (e.g. YSB's COUNT + MAX(ts) + SUM(revenue) ships
    only the revenue column, and a `firstUpdate` MIN(ts) costs nothing
    either).  Harvesters pick the per-window last/first-row array by
    each returned part's ``op``."""
    pos_field = "id" if spec.win_type is WinType.CB else "ts"
    dev = reducer.device_parts
    pos = [p for p in dev
           if p.op in ("max", "min") and p.field == pos_field]
    return [p for p in dev if p not in pos], pos


def _stat_parts(winfunc):
    return winfunc.parts if isinstance(winfunc, MultiReducer) else [winfunc]


def _host_free(spec: WindowSpec, winfunc) -> bool:
    """True when every stat is free on the host: counts come from window
    lengths, and ``max``/``min`` over the POSITION field (ts for TB, id
    for CB) are the last/first archived row's values — archives are kept
    ordered by position (stream_archive.hpp), so the host bookkeeping
    already holds the answers.  Such aggregates have no device-worthy
    compute at all."""
    pos_field = "id" if spec.win_type is WinType.CB else "ts"
    return all(p.op == "count"
               or (p.op in ("max", "min") and p.field == pos_field)
               for p in _stat_parts(winfunc))


def _multi_resident_ok(winfunc: MultiReducer) -> bool:
    """Whether a MultiReducer can run on the resident path: >=1 non-count
    stat, all ops resident-evaluable, no float-sum.  Stats over ONE field
    share a single ring; stats over several fields get one ring each
    (MultiFieldResidentExecutor)."""
    dev = winfunc.device_parts
    return (bool(dev) and all(p.op in _RESIDENT_OPS for p in dev)
            and not any(p.op == "sum"
                        and np.issubdtype(p.dtype, np.floating)
                        for p in dev))


def _arg_parts(parts):
    """The arg-extremum stats among `parts` (ops/functions.ArgReducer)."""
    return [p for p in parts if isinstance(p, ArgReducer)]


#: where the arg-extremum family runs, as the router's refusal and the
#: native core's own both say it
_ARGEXT_PLACEMENT = "one shard on one device (no mesh, shards=1)"


def _argext_misplaced(mesh, shards) -> bool:
    return mesh is not None or int(shards) != 1


def _native_refusal(dev_parts, max_fields: int):
    """Why the native resident core cannot take these device-worthy stats
    (what is left of a function after :func:`split_pos_max`), or None when
    it can.  The router routes around a refusal (the Python resident core
    takes what this one does not); the core itself raises it as a
    ``TypeError``.  ``max_fields`` is the library's ``wf_max_fields()``."""
    if not dev_parts:
        # a fully host-free aggregate forced onto the device: only the
        # Python core has the ship-the-position-column fallback
        return ("native resident core needs >=1 device-worthy stat "
                "after the pos-max split")
    fields = tuple(dict.fromkeys(p.field for p in dev_parts))
    if len(fields) > max_fields:
        return (f"native resident core stages at most {max_fields} "
                f"payload columns (got fields {fields})")
    if (_executor_family("native", dev_parts) != "regular"
            and any(np.issubdtype(p.dtype, np.floating) for p in dev_parts)):
        return ("native multi-field staging ships int64 columns; float "
                "stats run on the Python resident core")
    return None


def _executor_family(core: str, parts) -> str:
    """Step family (ops/resident.make_executor) the resident core `core`
    evaluates these device stats with; ``parts`` None is a
    JaxWindowFunction.  The native core gives every stat beyond the first
    its field's own ring (its single-stat form keeps the regular-descriptor
    compression) and an arg-extremum its own family; the Python core lets
    stats over ONE field share one ring."""
    if parts is None:
        return "multi"
    if core == "native":
        if _arg_parts(parts):
            return "argext"
        return "multi" if len(parts) > 1 else "regular"
    return "multi" if len({p.field for p in parts}) > 1 else "regular"


class CorePlan(NamedTuple):
    """What :func:`plan_core` decided for one window stage."""
    #: ``host`` (no device work: core/winseq and its vectorised kin),
    #: ``native`` (NativeResidentCore), ``resident_py`` (ResidentWinSeqCore)
    #: or ``restage`` (DeviceWinSeqCore)
    core: str
    #: the resident executor's step family — ``regular``, ``multi``,
    #: ``argext`` — or None off the resident path
    family: Optional[str]
    #: rings sharded over a mesh rather than placed on one device
    mesh: bool


def plan_core(spec, winfunc, *, use_resident=None, mesh=None, shards=1,
              native=None) -> CorePlan:
    """Which window core and executor family run ``winfunc`` over ``spec``
    — decided here and nowhere else, from the arguments alone: the same
    arguments give the same plan whatever ran earlier in the process.

    Resident-archive (each row crosses the wire once) when the function is
    a built-in monoid the resident executors evaluate, segment-restaging
    otherwise, the host core when there is no device work at all.
    ``native`` is what :func:`_native_core_fields` reports: the payload
    columns the native resident core stages, or None without it.  Raises
    ``ValueError`` for a request no device path can run."""
    on_mesh = mesh is not None
    parts = _stat_parts(winfunc)
    if _arg_parts(parts):
        # the row at a window's extremum: one executor family
        # (ops/resident.py wf_step_argext) under the native resident core,
        # for every role (SEQ, a Win_MapReduce's MAP and REDUCE alike).
        # Whatever that cannot run raises -- there is no host route here
        others = [p for p in parts if not isinstance(p, ArgReducer)]
        why = None
        if use_resident is False:
            why = "it runs on the resident path (no use_resident=False)"
        elif _argext_misplaced(mesh, shards):
            why = f"it runs {_ARGEXT_PLACEMENT}"
        elif any(p.op not in _RESIDENT_OPS + ("count",) for p in others) \
                or any(p.op == "sum" and np.issubdtype(p.dtype, np.floating)
                       for p in others):
            why = f"its sibling stats must be count or {_RESIDENT_OPS} " \
                  "over integers"
        elif native is None:
            why = "the native resident core is unavailable or opted out"
        if why is not None:
            raise ValueError(f"arg-extremum window function {winfunc!r} "
                             f"cannot run on the device: {why}")
        return CorePlan("native", "argext", False)
    if (isinstance(winfunc, (Reducer, MultiReducer))
            and use_resident is None and not on_mesh
            and _host_free(spec, winfunc)):
        # every stat is answerable from host bookkeeping (count from
        # window lengths; max over the position field from the
        # position-ordered archive) — shipping the column to the device
        # buys nothing but transfer traffic (YSB's count+MAX(ts) lost to
        # the host path for exactly this reason).  use_resident=True
        # forces the device.
        return CorePlan("host", None, False)
    if isinstance(winfunc, MultiReducer):
        # multi-stat windows are resident-only (the restaging executor has
        # no multi-output contract); count-only MultiReducers should be a
        # plain Reducer("count")
        if use_resident is False or not _multi_resident_ok(winfunc):
            raise ValueError(
                "MultiReducer runs on the resident device path only: "
                "needs >=1 non-count stat, ops in "
                f"{_RESIDENT_OPS}, no float sum (got {winfunc.parts})")
        # the C++ core carries the whole hot loop where it can; float
        # stats and wider functions keep the Python core, by its design.
        # With a mesh the rings shard P(kf, None) under either
        dev_parts, pos_parts = split_pos_max(spec, winfunc)
        core = ("native" if native is not None
                and _native_refusal(dev_parts, native) is None
                else "resident_py")
        return CorePlan(core, _executor_family(core, dev_parts or pos_parts),
                        on_mesh)
    jax_fn = isinstance(winfunc, JaxWindowFunction)
    if jax_fn and (use_resident or on_mesh):
        # arbitrary JAX window fns evaluate over multi-field resident
        # rings on request (use_resident=True); the default stays the
        # segment-restaging executor, whose staged columns carry each
        # launch's exact dtypes (rings are typed at allocation —
        # JaxWindowFunction.field_dtypes declares them).  The resident
        # path is the only one with a sharded-archive form, so mesh
        # implies it
        return CorePlan("resident_py", "multi", on_mesh)
    resident = use_resident
    if resident is None:
        resident = (isinstance(winfunc, Reducer)
                    and winfunc.op in _RESIDENT_OPS
                    # a float cumsum accumulates rounding error the host
                    # path's per-window reduction does not; floats keep the
                    # segment-restaging path unless the user opts in
                    and not (winfunc.op == "sum"
                             and np.issubdtype(winfunc.dtype, np.floating)))
    if on_mesh:
        if not (isinstance(winfunc, Reducer)
                and winfunc.op in _RESIDENT_OPS):
            raise ValueError(
                "mesh execution needs a resident-path Reducer "
                f"(one of {_RESIDENT_OPS}); got {winfunc!r}")
        if not resident:
            raise ValueError(
                "mesh execution requires the resident path: for float "
                "sums opt in explicitly with use_resident=True (cumsum "
                "rounding differs from the host's per-window reduction)")
    if not resident:
        return CorePlan("restage", None, False)
    # the C++ bookkeeping feeds the ring, sharded or not: a real pod's
    # multi-chip path must not re-pay the Python hot loop the native core
    # was built to kill; host key-shards compose with it — each shard owns
    # its own ring
    core = "native" if native is not None else "resident_py"
    return CorePlan(core, _executor_family(core, None if jax_fn
                                           else [winfunc]), on_mesh)


def _native_stream_refusal(spec, config, role, family, on_mesh, shards,
                           max_delay_ms=None, holdback=0):
    """Why the native resident core cannot close these windows on the
    stage's watermark (``fire_on="stream"``), or None when it can: the C++
    core holds rows back per key and releases them in order behind ONE
    clock, for time-based sliding or tumbling windows of a plain sequential
    worker whose stats the ``regular`` and ``multi`` families evaluate."""
    from ..core.windows import check_stream_fire
    check_stream_fire(spec, config, role, holdback)
    if spec.is_hopping:
        return ("fire_on='stream' on the device needs sliding or tumbling "
                "windows: hopping windows stay on the host window cores")
    if family == "argext":
        return ("fire_on='stream' on the device: the arg-extremum family "
                "reads its winning row back from an archive that follows "
                "arrival, which a held-back row would reorder")
    if on_mesh or int(shards) != 1:
        return ("fire_on='stream' on the device runs one shard on one "
                "device: a mesh or key shards would each keep a clock")
    if max_delay_ms is not None:
        return ("fire_on='stream' on the device follows the stream's time; "
                "max_delay_ms follows the wall clock")
    return None


def stream_fire_plan(plan: CorePlan, fire_on: str, spec=None, config=None,
                     role=Role.SEQ, shards=1, max_delay_ms=None,
                     holdback=0) -> CorePlan:
    """``plan`` itself if its core honours ``fire_on``.  ``"stream"`` runs
    on the host window cores and on the native resident core
    (:func:`_native_stream_refusal` says where that one cannot); a
    ``ValueError`` names the reason for every other core: the Python
    resident core and the restaging core fire a key's window on that key's
    next row."""
    if fire_on != "stream" or plan.core == "host":
        return plan
    if plan.core == "native":
        why = _native_stream_refusal(
            spec, config, role, plan.family, plan.mesh, shards, max_delay_ms,
            holdback)
        if why is None:
            return plan
        raise ValueError(why)
    raise ValueError(
        "fire_on='stream' runs on the host window cores (a count, or "
        "min/max over the time field) and on the native resident core: "
        f"this function is planned onto the {plan.core!r} core, which "
        "fires a key's window on that key's next row")


def _stream_burst_rows(batch_len, flush_rows) -> int:
    """The pieces a stream-time stage's fire leaves in (WinSeqNode
    ``burst_rows``), from the launch sizes the stage was given."""
    return max(int(batch_len), int(flush_rows) // 16)


def _native_core_fields():
    """The payload columns the native resident core stages
    (``wf_max_fields``), or None when the library is unavailable — also
    None under WF_NO_NATIVE_CORE=1, which pins the Python resident core."""
    import os
    if os.environ.get("WF_NO_NATIVE_CORE", "") == "1":
        return None
    from ..native import enabled
    lib = enabled()
    return None if lib is None else int(lib.wf_max_fields())


def make_device_core(worker, fn, dev_kw, index=0):
    """Build the device-batched core for a prototype host worker (a WinSeq
    carrying the farm's per-worker spec/config/role plumbing); ``index`` is
    the farm worker index driving per-worker device placement."""
    return make_core_for(worker.spec, fn, config=worker.config,
                         role=worker.role, map_indexes=worker.map_indexes,
                         result_ts_slide=worker.result_ts_slide,
                         worker_index=index,
                         dense_positions=worker.dense_positions, **dev_kw)


def make_core_for(spec, winfunc, *, batch_len=512, config=None,
                  role=Role.SEQ, map_indexes=(0, 1), result_ts_slide=None,
                  device=None, depth=None, compute_dtype=None,
                  use_resident=None, flush_rows=1 << 20, shards=1,
                  worker_index=0, mesh=None, max_delay_ms=None,
                  fire_on="key", holdback=0, dense_positions=False):
    """Build the window core :func:`plan_core` names.  With ``mesh`` the
    resident ring is sharded ``P('kf', None)`` across the mesh devices (one
    dispatch serves every key group over ICI); ``max_delay_ms`` is a timer
    on that core, whichever it is; ``fire_on="stream"`` with its
    ``holdback`` is the host cores' and the native resident core's
    (:func:`stream_fire_plan` refuses the others); ``dense_positions``
    (what a Pane_Farm knows of its window stage's input, core/winseq.py)
    goes to whichever core is built, and the native one does not act on
    it."""
    plan = stream_fire_plan(
        plan_core(spec, winfunc, use_resident=use_resident, mesh=mesh,
                  shards=shards, native=_native_core_fields()),
        fire_on, spec, config, role, shards, max_delay_ms, holdback)
    if plan.core == "host":
        from .win_seq import WinSeq
        return WinSeq(winfunc, spec.win_len, spec.slide_len,
                      spec.win_type, config=config, role=role,
                      map_indexes=map_indexes,
                      result_ts_slide=result_ts_slide,
                      fire_on=fire_on, holdback=holdback,
                      dense_positions=dense_positions).make_core()
    kw = dict(batch_len=batch_len, config=config, role=role,
              map_indexes=map_indexes, result_ts_slide=result_ts_slide,
              compute_dtype=compute_dtype, dense_positions=dense_positions)
    if plan.core == "restage":
        return DeviceWinSeqCore(
            spec, winfunc,
            device=resolve_worker_device(device, worker_index),
            depth=depth if depth is not None else 4, **kw)
    kw.update(flush_rows=flush_rows, device=device,
              depth=depth if depth is not None else 8,
              worker_index=worker_index, mesh=mesh, max_delay_ms=max_delay_ms)
    if plan.core == "native":
        from .native_core import NativeResidentCore
        return NativeResidentCore(spec, winfunc, shards=shards,
                                  fire_on=fire_on, holdback=holdback, **kw)
    return ResidentWinSeqCore(spec, winfunc, **kw)


def _refuse_container_input(winfunc, in_fields):
    """Raise where a device stage's function reads a sub-array field of the
    stage before it (a pane's container-valued partial): a device ring
    holds one scalar a row and field."""
    if isinstance(winfunc, JaxWindowFunction):
        read = winfunc.fields
    elif isinstance(winfunc, (Reducer, MultiReducer, ArgReducer)):
        read = winfunc.required_fields
    else:
        return      # a host function: `_host_standin` refuses it by name
    wide = [f for f in read if np.dtype(in_fields.get(f, np.int64)).shape]
    if wide:
        raise ValueError(
            f"a device WLQ cannot read the container-valued pane fields "
            f"{wide} (sub-array dtypes): a device ring holds one scalar a "
            "row and field; merge such panes on the host "
            "(wlq_on_device=False, pane_farm_gpu.hpp:176-201)")


class _DeviceCoreFactory:
    """Mixin for farm variants whose workers are device-batched: the host
    farm builds its prototype workers, `_make_core` swaps in the device
    core (set `_raw_fn` and `_dev_kw` before calling the farm ctor).
    Worker *i*'s executor lands on device ``i % n`` (resolve_worker_device)
    so a pardegree-n farm on an n-chip host owns one chip per worker."""

    def _make_core(self, worker, i=0):
        return make_device_core(worker, self._raw_fn, self._dev_kw, index=i)


class WinSeqTPU(_Pattern):
    """Sequential TPU window pattern (reference Win_Seq_GPU builder shape:
    withBatch(batch_len) replaces withBatch(batch_len, n_thread_block))."""

    def __init__(self, winfunc, win_len, slide_len, win_type=WinType.CB,
                 batch_len=512, name="win_seq_tpu",
                 config: PatternConfig = None, role: Role = Role.SEQ,
                 map_indexes=(0, 1), result_ts_slide=None, device=None,
                 depth=None, compute_dtype=None, use_resident=None,
                 flush_rows=1 << 20, shards=1, mesh=None, max_delay_ms=None,
                 fire_on="key", holdback=0, dense_positions=False):
        super().__init__(name, parallelism=1)
        self.spec = WindowSpec(win_len, slide_len, win_type)
        check_dense_positions(dense_positions, self.spec, fire_on)
        self._burst_rows = _stream_burst_rows(batch_len, flush_rows)
        self._kw = dict(batch_len=batch_len, config=config, role=role,
                        map_indexes=map_indexes,
                        result_ts_slide=result_ts_slide, device=device,
                        depth=depth, compute_dtype=compute_dtype,
                        use_resident=use_resident, flush_rows=flush_rows,
                        shards=shards, mesh=mesh,
                        max_delay_ms=max_delay_ms, fire_on=fire_on,
                        holdback=holdback, dense_positions=dense_positions)
        self.winfunc = winfunc

    def make_core(self):
        return make_core_for(self.spec, self.winfunc, **self._kw)

    @property
    def result_schema(self):
        return Schema(**self.winfunc.result_fields)

    def _make_replica(self, i):
        node = WinSeqNode(self.make_core(), f"{self.name}.{i}")
        node.burst_rows = self._burst_rows
        node.ctx = RuntimeContext(1, 0, self.name)
        return node


class WinFarmTPU(_DeviceCoreFactory, WinFarm):
    """Win_Farm of device-batched window cores — the reference's
    Win_Farm_GPU (win_farm_gpu.hpp:132-168: same emitter/collector as the
    CPU farm, device workers). On one chip, workers share the device and
    their async launch queues interleave (replacing per-worker CUDA
    streams); multi-chip distribution is the mesh layer's job
    (parallel/)."""

    #: a progress row sets off a worker's launch: the emitter gives that
    #: worker its turn (patterns/win_farm.py)
    hands_over = True

    def __init__(self, winfunc, win_len, slide_len, win_type=WinType.CB,
                 pardegree=2, batch_len=512, name="win_farm_tpu",
                 ordered=True, n_emitters=1, config=None, role=Role.SEQ,
                 device=None, depth=None, compute_dtype=None,
                 use_resident=None, flush_rows=1 << 20, max_delay_ms=None,
                 dense_positions=False):
        self._raw_fn = winfunc
        self._dev_kw = dict(batch_len=batch_len, device=device, depth=depth,
                            compute_dtype=compute_dtype,
                            use_resident=use_resident, flush_rows=flush_rows,
                            max_delay_ms=max_delay_ms)
        super().__init__(_host_standin(winfunc), win_len, slide_len, win_type,
                         pardegree=pardegree, name=name, ordered=ordered,
                         n_emitters=n_emitters, config=config, role=role,
                         dense_positions=dense_positions)


class KeyFarmTPU(_DeviceCoreFactory, KeyFarm):
    """Key_Farm of device-batched window cores (key_farm_gpu.hpp:151-161).
    Keys stay resident per worker; the mesh layer maps workers to cores
    over ICI with no collectives (SURVEY.md §7)."""

    def __init__(self, winfunc, win_len, slide_len, win_type=WinType.CB,
                 pardegree=2, batch_len=512, name="key_farm_tpu",
                 routing=None, config=None, role=Role.SEQ, device=None,
                 depth=None, compute_dtype=None, use_resident=None,
                 flush_rows=1 << 20, max_delay_ms=None, fire_on="key",
                 holdback=0):
        self._raw_fn = winfunc
        self._dev_kw = dict(batch_len=batch_len, device=device, depth=depth,
                            compute_dtype=compute_dtype,
                            use_resident=use_resident, flush_rows=flush_rows,
                            max_delay_ms=max_delay_ms, fire_on=fire_on,
                            holdback=holdback)
        self.burst_rows = _stream_burst_rows(batch_len, flush_rows)
        super().__init__(_host_standin(winfunc), win_len, slide_len, win_type,
                         pardegree=pardegree, name=name, routing=routing,
                         config=config, role=role, fire_on=fire_on,
                         holdback=holdback)


class PaneFarmTPU(PaneFarm):
    """Pane_Farm with per-stage device placement — the 4 constructor
    families of Pane_Farm_GPU (pane_farm_gpu.hpp:176-480) become two
    booleans; an incremental stage always runs on the host (the reference
    likewise pairs INC stages with host execution).

    A device PLQ may be a user's function whose result is a container
    (``JaxWindowFunction(count_field=)``: a pane's partial in fixed-width
    sub-array slots, the reference's arbitrary ``result_t``) under a host
    WLQ that merges them (pane_farm_gpu.hpp:176-201, the device-PLQ
    families), at every ``opt_level``.  A device WLQ over such panes is
    refused here: a ring holds one scalar a row and field."""

    def __init__(self, plq_func, wlq_func, win_len, slide_len,
                 win_type=WinType.CB, plq_degree=1, wlq_degree=1,
                 name="pane_farm_tpu", plq_on_device=True, wlq_on_device=True,
                 batch_len=512, device=None, depth=None, compute_dtype=None,
                 use_resident=None, flush_rows=1 << 20, max_delay_ms=None,
                 **kw):
        self._on_device = {"plq": plq_on_device, "wlq": wlq_on_device}
        self._dev_kw = dict(batch_len=batch_len, device=device, depth=depth,
                            compute_dtype=compute_dtype,
                            use_resident=use_resident, flush_rows=flush_rows,
                            max_delay_ms=max_delay_ms)
        if wlq_on_device and not kw.get("wlq_incremental"):
            _refuse_container_input(
                wlq_func, kw.get("plq_result_fields")
                or getattr(plq_func, "result_fields", None) or {})
        super().__init__(plq_func, wlq_func, win_len, slide_len, win_type,
                         plq_degree=plq_degree, wlq_degree=wlq_degree,
                         name=name, **kw)

    def _make_stage(self, which, func, win, slide, wt, degree, name,
                    incremental, result_fields, ordered, role,
                    dense_positions=False):
        if not self._on_device.get(which) or incremental:
            return super()._make_stage(which, func, win, slide, wt, degree,
                                       name, incremental, result_fields,
                                       ordered, role,
                                       dense_positions=dense_positions)
        _host_standin(func)     # a host function is refused here, by name
        cfg = self.config
        if degree > 1:
            return WinFarmTPU(func, win, slide, wt, pardegree=degree,
                              name=name, ordered=ordered, config=cfg,
                              role=role, dense_positions=dense_positions,
                              **self._dev_kw)
        seq_cfg = PatternConfig(cfg.id_inner, cfg.n_inner, cfg.slide_inner,
                                0, 1, slide)
        return WinSeqTPU(func, win, slide, wt, name=name, config=seq_cfg,
                         role=role, dense_positions=dense_positions,
                         **self._dev_kw)

    def clone_with(self, name, slide_len=None, config=None, ordered=False):
        kw = dict(self._proto)
        if slide_len is not None:
            kw["slide_len"] = slide_len
        return PaneFarmTPU(name=name, config=config, ordered=ordered,
                           plq_on_device=self._on_device["plq"],
                           wlq_on_device=self._on_device["wlq"],
                           **self._dev_kw, **kw)


class WinMapReduceTPU(WinMapReduce):
    """Win_MapReduce with per-stage device placement
    (win_mapreduce_gpu.hpp:171-521)."""

    def __init__(self, map_func, reduce_func, win_len, slide_len,
                 win_type=WinType.CB, map_degree=2, reduce_degree=1,
                 name="win_mr_tpu", map_on_device=True,
                 reduce_on_device=False, batch_len=512, device=None,
                 depth=None, compute_dtype=None, use_resident=None,
                 flush_rows=1 << 20, max_delay_ms=None, **kw):
        self._on_device = {"map": map_on_device, "reduce": reduce_on_device}
        self._dev_kw = dict(batch_len=batch_len, device=device, depth=depth,
                            compute_dtype=compute_dtype,
                            use_resident=use_resident, flush_rows=flush_rows,
                            max_delay_ms=max_delay_ms)
        super().__init__(map_func, reduce_func, win_len, slide_len, win_type,
                         map_degree=map_degree, reduce_degree=reduce_degree,
                         name=name, **kw)

    def _make_map_stage(self, map_func, n, name, incremental, result_fields):
        from .win_mapreduce import _MapStage
        if not self._on_device["map"] or incremental:
            return super()._make_map_stage(map_func, n, name, incremental,
                                           result_fields)
        return _MapStage(_host_standin(map_func), self.spec, n, name, None,
                         result_fields, self.config, device_fn=map_func,
                         device_opts=self._dev_kw)

    def _make_reduce_stage(self, reduce_func, n, degree, name, incremental,
                           result_fields, ordered):
        if not self._on_device["reduce"] or incremental:
            return super()._make_reduce_stage(reduce_func, n, degree, name,
                                              incremental, result_fields,
                                              ordered)
        cfg = self.config
        if degree > 1:
            return WinFarmTPU(reduce_func, n, n, WinType.CB, pardegree=degree,
                              name=name, ordered=ordered, config=cfg,
                              role=Role.REDUCE, **self._dev_kw)
        red_cfg = PatternConfig(cfg.id_inner, cfg.n_inner, cfg.slide_inner,
                                0, 1, n)
        return WinSeqTPU(reduce_func, n, n, WinType.CB, name=name,
                         config=red_cfg, role=Role.REDUCE, **self._dev_kw)

    def clone_with(self, name, slide_len=None, config=None, ordered=False):
        kw = dict(self._proto)
        if slide_len is not None:
            kw["slide_len"] = slide_len
        return WinMapReduceTPU(name=name, config=config, ordered=ordered,
                               map_on_device=self._on_device["map"],
                               reduce_on_device=self._on_device["reduce"],
                               **self._dev_kw, **kw)
