"""Device-resident window archives: each stream row crosses the host→device
wire ONCE and window evaluation reads HBM.

This is the second-generation device path (the first, ``device.py``, restages
every fired window's archive segment per batch, mirroring the reference's
per-batch ``cudaMemcpyAsync`` of ``Bin`` — win_seq_gpu.hpp:451-476).  Host→
device transfers and the per-dispatch launch service are what a launch pays
for; what they cost on the chip in use is measured per run (stats_snapshot's
``mean_launch_ms``), not assumed.  The design:

* keeps a per-key **ring archive** resident on the device: a ``(KP, cap)``
  array whose row ``r`` holds the live tuples of dense-key ``r`` in arrival
  order (the device twin of ``core/archive.py``'s host ``KeyArchive``);
* appends each chunk's new rows as ONE rectangle in the **narrowest dtype**
  that holds the chunk's value range (int8/int16/int32/float32), widened to
  the accumulate dtype on device;
* fuses append + window evaluation into ONE dispatch per launch: a vmapped
  ``dynamic_update_slice`` writes the rectangle at per-key offsets, then
  either a ring-wide ``cumsum`` + two-point gather (sum/mean — O(B) gathered
  elements instead of O(B·win)) or a masked ``(B, pad)`` gather-reduce
  (min/max) evaluates every fired window;
* fetches results asynchronously (``copy_to_host_async``) with bounded
  depth, so steady state pipelines H2D, compute, and D2H.

The host side (``ResidentWinSeqCore`` in patterns/win_seq_tpu.py) owns all
bookkeeping — write offsets, ring rebase, window descriptors — so this
executor is a dumb, replayable launch queue, like the reference's per-worker
``cudaStream_t`` (win_seq_gpu.hpp:294).
"""

from __future__ import annotations

import threading
import weakref
from collections import deque

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..utils import profile
from .backend import default_device
from .device import _bucket, _bucket_fine
from .monoid import identity as _identity

#: process-wide compiled-step cache (executors are per-pattern-instance,
#: the executables they compile should outlive them)
_STEP_CACHE = {}
#: step-cache keys added by prewarm_regular_ladder (never seed ladders)
_PREWARMED = set()
#: steps bound to a user's window function (JaxWindowFunction.fn), a dict of
#: them per function object: every executor given the same function finds
#: them again (a warm-up pipeline's steps serve the pipeline after it), and
#: they go when the function does -- the steps hold it weakly (_weak_fn)
_FN_STEP_CACHE = weakref.WeakKeyDictionary()
_FN_STEP_MU = threading.Lock()

# -- launch diagnostics (always on: one lock round-trip per dispatch) -------
# Every resident dispatch feeds these process-wide counters: dispatch count,
# merge count (launches fused by wf_launch_coalesce), and wall service time
# from the end of dispatch to the end of the harvest — the stamps the
# launch's own ``dispatch`` and ``harvest_wait`` spans took (utils/profile),
# not a second reading of the clock — a run's result carries them so a slow
# launch service can be told from a slow host loop.

_STATS_MU = threading.Lock()
_STATS = {"dispatches": 0, "merges": 0, "svc_s_sum": 0.0, "svc_n": 0,
          # steps built around a user's window function (_make_multi_step)
          "udf_step_builds": 0}


def stats_add(name: str, value=1):
    with _STATS_MU:
        _STATS[name] = _STATS.get(name, 0) + value


def stats_snapshot(reset: bool = False) -> dict:
    """{"dispatches", "merges", "udf_step_builds", "mean_launch_ms"} since
    the last reset."""
    with _STATS_MU:
        snap = dict(_STATS)
        if reset:
            for k in _STATS:
                _STATS[k] = 0
    n = snap.pop("svc_n")
    s = snap.pop("svc_s_sum")
    snap["mean_launch_ms"] = round(1e3 * s / n, 2) if n else 0.0
    return snap

_REDUCE_OPS = ("sum", "min", "max", "prod")

#: (launch id, shard, cause) of a launch nobody named (the Python resident
#: core's): its spans carry no id
_NO_TAG = (None, None, None)


def _named_jit(fn, name: str, **jit_kw):
    """``jax.jit`` of `fn` under a name that says the step's family, so
    the trace's ``XLA Modules`` line reads ``jit_<name>(...)`` per family
    instead of one ``jit_step`` for all."""
    def call(*args):
        return fn(*args)
    call.__name__ = call.__qualname__ = name
    return jax.jit(call, **jit_kw)


class RingSnapshot:
    """Checkpoint handle over a resident ring archive (recovery layer,
    docs/ROBUSTNESS.md "Recovery").

    Grabbing one is cheap: jax arrays are functional, so holding the
    current ring reference IS a consistent copy — each later launch
    produces a *new* ring array and never mutates this one.  The
    device→host transfer starts immediately (``copy_to_host_async``) but
    materialises only at :meth:`resolve` — on the checkpoint writer
    thread — so the copy overlaps the ring's ongoing compute instead of
    stalling it (the CTA-pipelining hide-latency-with-stages idiom
    applied to snapshots)."""

    __slots__ = ("rings", "KP", "cap")

    def __init__(self, rings, KP: int, cap: int):
        self.rings = rings      # tuple of device arrays, or None (lazy ring)
        self.KP = KP
        self.cap = cap
        if rings is not None:
            for r in rings:
                r.copy_to_host_async()

    def resolve(self) -> dict:
        """Materialise to host numpy (pickle-ready)."""
        rings = (None if self.rings is None
                 else tuple(np.asarray(r) for r in self.rings))
        return {"rings": rings, "KP": self.KP, "cap": self.cap}


def _pad2(a, rows, cols):
    out = np.zeros((rows, cols), dtype=a.dtype)
    out[:a.shape[0], :a.shape[1]] = a
    return out


def _pad1(a, size, dtype=np.int32):
    out = np.zeros(size, dtype=dtype)
    out[:len(a)] = a
    return out


def _check_ring_overflow(offs, Rb, cap):
    """dynamic_update_slice clamps the start, which would silently
    overwrite live cells near the ring end — the host core's rebase
    invariant must prevent ever getting here."""
    if len(offs) and int(offs.max()) + Rb > cap:
        raise ValueError(
            f"ring overflow: offset {int(offs.max())} + {Rb} > {cap}")


def _regular_body(cap, C, slide, acc_dt, ring, blk, offs, rstart0, rlen):
    """Fused append + regular-window sum over one ring (block): window i of
    ring row r starts at rstart0[r] + i*slide with length rlen[r] — the
    descriptors are expanded on the device from per-key scalars via an
    iota.  Returns (ring, (rows, C) sums)."""
    blk = blk.astype(acc_dt)
    ring = jax.vmap(
        lambda row, b, o: lax.dynamic_update_slice(row, b, (o,))
    )(ring, blk, offs)
    cs = jnp.cumsum(ring, axis=1)
    cs = jnp.pad(cs, ((0, 0), (1, 0)))
    iota = jnp.arange(C, dtype=jnp.int32)
    s2 = jnp.clip(rstart0[:, None] + iota[None, :] * slide, 0, cap)
    e2 = jnp.clip(s2 + rlen[:, None], 0, cap)
    rows = jnp.arange(ring.shape[0], dtype=jnp.int32)[:, None]
    out = cs[rows, e2] - cs[rows, s2]
    return ring, out


def _make_regular_step(key):
    (_, _op, cap, R, KP, C, blk_dt, acc_dt, slide) = key
    acc_dt = np.dtype(acc_dt)

    def step(ring, blk, offs, rcount, rstart0, rlen):
        return _regular_body(cap, C, slide, acc_dt, ring, blk, offs,
                             rstart0, rlen)

    return _named_jit(step, "wf_step_regular")


def _make_mesh_regular_step(key):
    """Sharded regular step: shard_map of :func:`_regular_body` over the
    key-group axis — each device appends its row block and expands its own
    per-key arithmetic window sequences (no collectives, like the plain
    mesh step)."""
    (_tag, _op, cap, Rb, KP, C, blk_dt, acc_dt, slide, mesh, axis) = key
    acc_dt = np.dtype(acc_dt)
    from jax.sharding import PartitionSpec as P

    def local(ring, blk, offs, rcount, rstart0, rlen):
        return _regular_body(cap, C, slide, acc_dt, ring, blk, offs,
                             rstart0, rlen)

    mapped = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axis, None), P(axis, None), P(axis), P(axis), P(axis),
                  P(axis)),
        out_specs=(P(axis, None), P(axis, None)))
    return _named_jit(mapped, "wf_step_regular_mesh")


def _ring_append(ring, blk, offs, acc_dt):
    """Vmapped per-row append: write each key's new-row slice at its ring
    offset, widening the wire dtype to the accumulate dtype."""
    blk = blk.astype(acc_dt)
    return jax.vmap(
        lambda row, b, o: lax.dynamic_update_slice(row, b, (o,))
    )(ring, blk, offs)


def _ring_eval(op, cap, pad, acc_dt, ring, rows, starts, lens):
    """Evaluate one monoid over every described window: cumsum two-point
    gather (sum) or masked (B, pad) gather-reduce (min/max/prod)."""
    if op == "sum":
        cs = jnp.cumsum(ring, axis=1)
        cs = jnp.pad(cs, ((0, 0), (1, 0)))
        return cs[rows, starts + lens] - cs[rows, starts]
    idx = jnp.minimum(
        starts[:, None] + jnp.arange(pad, dtype=jnp.int32)[None, :],
        cap - 1)
    vals = ring[rows[:, None], idx]
    mask = jnp.arange(pad, dtype=jnp.int32)[None, :] < lens[:, None]
    ident = jnp.asarray(_identity(op, acc_dt), dtype=acc_dt)
    red = {"min": jnp.min, "max": jnp.max, "prod": jnp.prod}[op]
    return red(jnp.where(mask, vals, ident), axis=1)


def _append_eval(ops, cap, pad, acc_dt, ring, blk, offs, rows, starts,
                 lens):
    """The shared fused append + window-eval body — one append, then every
    stat of `ops` evaluated over the same ring (multi-stat: e.g. YSB's
    sum/max over one shipped column set in one dispatch).  Returns the ring
    and one output per op."""
    ring = _ring_append(ring, blk, offs, acc_dt)
    outs = tuple(_ring_eval(op, cap, pad, acc_dt, ring, rows, starts, lens)
                 for op in ops)
    return ring, outs


def _make_step(key):
    """Build + jit the fused append+eval step for one shape bucket."""
    (ops, cap, R, B, KP, blk_dt, acc_dt, pad) = key
    acc_dt = np.dtype(acc_dt)

    def step(ring, blk, offs, wrows, wstarts, wlens):
        ring, outs = _append_eval(ops, cap, pad, acc_dt, ring, blk, offs,
                                  wrows, wstarts, wlens)
        return ring, (outs[0] if len(outs) == 1 else outs)

    return _named_jit(step, "wf_step_append_eval")


def _make_mesh_step(key):
    """Build + jit the sharded fused append+eval step: shard_map over the
    key-group axis — each device appends to and evaluates windows over its
    own row block of the ring (key groups are embarrassingly parallel, so
    the program has no collectives; the sharding just keeps each group's
    archive in its own chip's HBM)."""
    (_, ops, cap, Rb, Bs, KP, blk_dt, acc_dt, pad, mesh, axis) = key
    acc_dt = np.dtype(acc_dt)
    from jax.sharding import PartitionSpec as P

    def local(ring, blk, offs, lrows, lstarts, llens):
        # per-shard views: ring (rps, cap), blk (rps, Rb), offs (rps,),
        # descriptors (1, Bs) — local rows/starts/lens of this shard's
        # windows (host pre-grouped them per shard)
        ring, outs = _append_eval(ops, cap, pad, acc_dt, ring, blk, offs,
                                  lrows[0], lstarts[0], llens[0])
        outs = tuple(o[None, :] for o in outs)
        return ring, (outs[0] if len(outs) == 1 else outs)

    mapped = jax.shard_map(
        local, mesh=mesh,
        in_specs=(P(axis, None), P(axis, None), P(axis),
                  P(axis, None), P(axis, None), P(axis, None)),
        out_specs=(P(axis, None), P(axis, None)))
    return _named_jit(mapped, "wf_step_append_eval_mesh")


class ResidentWindowExecutor:
    """Launch queue over a device-resident ring archive.

    The caller fully specifies each dispatch (rectangle, offsets, window
    descriptors in ring coordinates); this class handles shape bucketing,
    dtype narrowing/widening, the ring array's lifetime, and asynchronous
    result harvest.  ``op`` is one of sum/min/max/prod ("count" needs no
    device work — the host core answers it from window lengths; "mean" is
    answered by the segment-restaging path, ops/device.py).
    """

    #: the newest dispatch's device result, replaced by the dispatching
    #: thread: the one value ring_idle() reads from any other thread
    _last_out = None
    #: which call of its node's thread takes a harvested result on, written
    #: as ``handed`` onto the launch's ``harvest_wait`` record: ``svc`` or
    #: ``wake``, set by a core that harvests on that thread itself (the
    #: Python resident core); None where another thread harvests and the
    #: core amends the record at its hand-over (the native core)
    handed = None

    def __init__(self, op, device=None, depth: int = 8,
                 acc_dtype=np.int32):
        # `op` is one reduce op or a tuple of them: every op evaluates over
        # the SAME ring in one fused dispatch (multi-stat windows — the
        # device side of ops.functions.MultiReducer)
        self.single = isinstance(op, str)
        self.ops = (op,) if self.single else tuple(op)
        for o in self.ops:
            if o not in _REDUCE_OPS:
                raise ValueError(f"unsupported resident op {o!r}")
        if not self.ops:
            raise ValueError("need at least one resident op")
        self.op = self.ops[0]
        self.device = device or default_device()
        self.depth = depth
        self.acc_dtype = np.dtype(acc_dtype)
        self.cap = 0          # ring columns (set on first reset)
        self.KP = 0           # ring rows (padded key count)
        self._ring = None
        # (meta, sel, device_out, t_dispatched_ns, (launch, shard, cause))
        self._inflight = deque()
        self._ready = []
        self._svc = deque(maxlen=32)   # recent dispatch→ready seconds
        self._svc_mean = 0.0
        self.dispatches = 0   # launches this executor sent to its device

    # ------------------------------------------------------------ lifecycle

    def reset(self, n_keys: int, cap: int):
        """(Re)allocate an empty ring of at least (n_keys, cap); contents
        are repopulated by the next launch's rectangle (host rebase)."""
        self.KP = _bucket(max(n_keys, 1))
        self.cap = _bucket(max(cap, 16))
        self._ring = None  # lazily zeros on next launch

    def _ring_arr(self):
        if self._ring is None:
            self._ring = jax.device_put(
                jnp.zeros((self.KP, self.cap), dtype=self.acc_dtype),
                self.device)
        return self._ring

    # ---------------------------------------------------- checkpoint/restore

    def _ring_placement(self):
        """Where restored rings land (mesh executors override with their
        NamedSharding)."""
        return self.device

    def _rings_tuple(self):
        """Current ring array(s) as a tuple, or None if lazily unbuilt
        (the multi-field executor overrides the pair of accessors; the
        checkpoint methods below are shared)."""
        return None if self._ring is None else (self._ring,)

    def _rings_assign(self, rings):
        self._ring = None if rings is None else rings[0]

    def ring_snapshot(self) -> RingSnapshot:
        """Consistent-copy handle of the ring(s) (caller must have
        drained in-flight launches first — their appends are already IN
        this ring version, but their undelivered results would be
        lost)."""
        if self._inflight:
            raise RuntimeError("ring_snapshot with launches in flight; "
                               "drain() first")
        return RingSnapshot(self._rings_tuple(), self.KP, self.cap)

    def ring_restore(self, snap):
        """Reinstate a snapshot (RingSnapshot or its resolved dict) and
        clear the launch queue."""
        data = snap.resolve() if isinstance(snap, RingSnapshot) else snap
        self._inflight.clear()
        self._ready = []
        self._last_out = None
        self.KP = data["KP"]
        self.cap = data["cap"]
        rings = data["rings"]
        self._rings_assign(None if rings is None else tuple(
            jax.device_put(r, self._ring_placement()) for r in rings))

    def invalidate(self):
        """Drop the ring(s) and launch queue entirely: the owning
        core's next flush rebases, rebuilding the ring from host-live
        archive rows (the no-ring-snapshot restore path)."""
        self._inflight.clear()
        self._ready = []
        self._last_out = None
        self._rings_assign(None)
        self.KP = 0
        self.cap = 0

    # ------------------------------------------------------------- dispatch

    def narrow(self, vals: np.ndarray) -> np.dtype:
        """Narrowest wire dtype holding `vals` exactly, capped by the
        accumulate dtype: ints narrow to int8/int16/int32 (int64 allowed
        when accumulating in a 64-bit dtype); floats ship in the
        accumulate precision."""
        wide = self.acc_dtype.itemsize >= 8
        if vals.dtype.kind == "f":
            return np.dtype(np.float64 if wide else np.float32)
        if not len(vals):
            return np.dtype(np.int8)
        lo, hi = int(vals.min()), int(vals.max())
        ladder = (np.int8, np.int16, np.int32, np.int64) if wide else \
                 (np.int8, np.int16, np.int32)
        for dt in ladder:
            info = np.iinfo(dt)
            if info.min <= lo and hi <= info.max:
                return np.dtype(dt)
        return np.dtype(ladder[-1])  # wraps; the core warned at
        # construction when the result dtype exceeds the accumulate dtype

    def launch(self, meta, blk: np.ndarray, offs: np.ndarray,
               wrows: np.ndarray, wstarts: np.ndarray, wlens: np.ndarray,
               tag=_NO_TAG):
        """One fused append+eval dispatch.

        blk: (K, R) new rows per dense key (narrow dtype, zero-padded);
        offs: (K,) per-key ring write offsets; wrows/wstarts/wlens: (B,)
        fired-window descriptors in ring coordinates.  `meta` is returned
        with the results at harvest.  Caller guarantees offs + R <= cap.
        `tag` is the launch's (id, shard, cause), carried on its spans.
        """
        K, R = blk.shape
        if K > self.KP:
            raise ValueError("rectangle exceeds ring rows; reset() first")
        B = len(wstarts)
        Rb = _bucket(max(R, 1))
        Bb = _bucket(max(B, 1))
        _check_ring_overflow(offs, Rb, self.cap)
        pad = (_bucket(int(wlens.max()) if B else 1)
               if any(o != "sum" for o in self.ops) else 0)
        key = (self.ops, self.cap, Rb, Bb, self.KP, blk.dtype.str,
               self.acc_dtype.str, pad)
        fn = _STEP_CACHE.get(key)
        if fn is None:
            fn = _STEP_CACHE[key] = _make_step(key)
        with profile.span("device_put", *tag):
            blkp = (blk if blk.shape == (self.KP, Rb)
                    else _pad2(blk, self.KP, Rb))
            args = jax.device_put(
                (blkp, _pad1(offs, self.KP),
                 _pad1(wrows, Bb), _pad1(wstarts, Bb), _pad1(wlens, Bb)),
                self.device)
        profile.add("bytes_shipped", blk.nbytes)
        profile.add("rows_shipped", blk.size)
        profile.add("windows", B)
        with profile.span("dispatch", *tag) as sp:
            self._ring, out = fn(self._ring_arr(), *args)
            for o in (out if isinstance(out, tuple) else (out,)):
                o.copy_to_host_async()
        self._dispatched(meta, B, out, sp, tag)

    def launch_regular(self, meta, blk: np.ndarray, offs: np.ndarray,
                       rcount: np.ndarray, rstart0: np.ndarray,
                       rlen: np.ndarray, slide: int, wrows: np.ndarray,
                       widx: np.ndarray, cmax: int = 0, tag=_NO_TAG):
        """Fused append+eval with *regular* window descriptors: per ring
        row, windows i in [0, rcount[r]) start at rstart0[r] + i*slide with
        length rlen[r] — only 3 per-key scalars cross the wire instead of
        3 arrays of B int32 (sum only; the host maps the (KP, C) result
        back to pending-window order via (wrows, widx))."""
        if not (self.single and self.op == "sum"):
            raise ValueError("regular descriptors implemented for "
                             "single-stat sum")
        K, R = blk.shape
        if K > self.KP:
            raise ValueError("rectangle exceeds ring rows; reset() first")
        Rb = _bucket(max(R, 1))
        C = _bucket(int(cmax) if cmax else
                    (int(rcount.max()) if len(rcount) else 1))
        _check_ring_overflow(offs, Rb, self.cap)
        key = ("reg", self.op, self.cap, Rb, self.KP, C, blk.dtype.str,
               self.acc_dtype.str, int(slide))
        fn = _STEP_CACHE.get(key)
        if fn is None:
            fn = _STEP_CACHE[key] = _make_regular_step(key)
        with profile.span("device_put", *tag):
            blkp = (blk if blk.shape == (self.KP, Rb)
                    else _pad2(blk, self.KP, Rb))
            args = jax.device_put(
                (blkp, _pad1(offs, self.KP),
                 _pad1(rcount, self.KP), _pad1(rstart0, self.KP),
                 _pad1(rlen, self.KP)),
                self.device)
        profile.add("bytes_shipped", blk.nbytes)
        profile.add("rows_shipped", blk.size)
        profile.add("windows", len(wrows))
        with profile.span("dispatch", *tag) as sp:
            self._ring, out = fn(self._ring_arr(), *args)
            out.copy_to_host_async()
        self._dispatched(meta, (np.asarray(wrows), np.asarray(widx)), out,
                         sp, tag)

    def _dispatched(self, meta, sel, out, sp, tag):
        """Queue a dispatched launch for harvest, stamped with the end of
        its ``dispatch`` span `sp`; harvest beyond the depth bound."""
        self.dispatches += 1
        stats_add("dispatches")
        self._last_out = out
        self._inflight.append((meta, sel, out, sp.end_ns(), tag))
        while len(self._inflight) > self.depth:
            self._harvest_one("depth")

    # -------------------------------------------------------------- harvest

    def _note_service(self, dt_ns: int, ready: bool, how: str):
        """One launch closed: `dt_ns` from the end of its dispatch to the
        end of its harvest, `ready` whether its result was there before
        the harvest began, `how` what made the thread harvest it."""
        dt = dt_ns / 1e9
        profile.add("launches")
        if ready:
            profile.add("launches_ready_at_poll")
        if how == "wait":
            profile.add("harvest_waited")
        self._svc.append(dt)
        # fold the window mean here, on the harvesting thread: readers on
        # OTHER threads (the early-flush guard runs on the node thread)
        # then see one atomic float instead of iterating a deque that a
        # ship thread is appending to
        self._svc_mean = sum(self._svc) / len(self._svc)
        stats_add("svc_s_sum", dt)
        stats_add("svc_n", 1)

    def mean_service_s(self) -> float:
        """Mean wall time of recent launches from the end of dispatch to
        the end of harvest.  Under a thread that waits on its oldest launch
        (harvest_oldest: the native core's ship threads) that is the ring's
        own service — the device step, the copy to the host and the fetch;
        where launches are harvested only at a caller's poll (the
        synchronous path, a Python resident core that nothing wakes) it
        also holds the wait for that poll.  Safe to read from any thread."""
        return self._svc_mean

    def _harvest_one(self, how: str, ready: bool = None):
        """Close the oldest launch; `how` names what made the thread do it
        (the ``harvest`` field of the launch's ``harvest_wait`` record):
        ``wait`` the thread's own wait on it, ``poke`` a caller's poll that
        found it ready, ``depth`` more than `depth` in flight, ``drain``."""
        meta, sel, out, t_dispatched, tag = self._inflight[0]
        if ready is None:
            # read once, before blocking: a launch whose result was
            # already there spent the rest of its service waiting for
            # this harvest, not for the device
            ready = self._is_ready(out)
        with profile.span("harvest_wait", *tag) as sp:
            sp.extra = {"ready": ready, "harvest": how}
            if self.handed is not None:
                sp.extra["handed"] = self.handed
            res = self._fetch(sel, out)
        # only now: a fetch that raised leaves its launch in flight, for
        # the next harvest to try again
        self._inflight.popleft()
        self._note_service(sp.end_ns() - t_dispatched, ready, how)
        self._ready.append((meta, res))

    @staticmethod
    def _fetch(sel, out):
        """Block on one launch's device result and cut it to its windows."""
        multi = isinstance(out, tuple)
        arrs = [np.asarray(o) for o in out] if multi else [np.asarray(out)]
        if isinstance(sel, tuple):   # regular/mesh: index map -> flat (B,)
            arrs = [a[sel[0], sel[1]] for a in arrs]
        else:
            arrs = [a[:sel] for a in arrs]
        return tuple(arrs) if multi else arrs[0]

    def poll(self):
        """Harvest completed launches without blocking on the rest."""
        while self._inflight and self._is_ready(self._inflight[0][2]):
            self._harvest_one("poke", ready=True)
        ready, self._ready = self._ready, []
        return ready

    def harvest_oldest(self):
        """Block on the oldest launch in flight (its copy to the host was
        started at dispatch; the wait releases the interpreter lock), then
        poll().  For the thread that dispatches only, with a launch in
        flight."""
        self._harvest_one("wait")
        return self.poll()

    def unready_count(self) -> int:
        """Dispatches still being serviced by the device (the ship
        throttle's saturation signal).  For the thread that dispatches
        only: it walks the in-flight queue."""
        return sum(1 for entry in self._inflight
                   if not self._is_ready(entry[2]))

    def ring_idle(self) -> bool:
        """Whether the device has served everything this executor sent
        it: a ring's launches run in dispatch order, so the newest one's
        result being there says all are.  Safe to read from any thread."""
        out = self._last_out
        return out is None or self._is_ready(out)

    @staticmethod
    def _is_ready(out) -> bool:
        if isinstance(out, tuple):
            return all(o.is_ready() for o in out)
        return out.is_ready()

    @staticmethod
    def wait_ready(out):
        """Block until one launch's device result `out` is ready (the wait
        releases the interpreter lock).  For any thread: it reads nothing
        of the executor."""
        jax.block_until_ready(out)

    def drain(self):
        # EOS drain taper: issue async D2H copies for EVERY in-flight
        # result before the serial harvest blocks on the first — the
        # remaining launches' compute and result copies then overlap the
        # waits instead of each paying its own synchronisation, strictly
        # in arrival order
        for entry in self._inflight:
            out = entry[2]
            for o in (out if isinstance(out, tuple) else (out,)):
                o.copy_to_host_async()
        while self._inflight:
            self._harvest_one("drain")
        ready, self._ready = self._ready, []
        return ready


def _weak_fn(fn):
    """A call that gives `fn` back without keeping it alive: a step cached
    under its function (_FN_STEP_CACHE) must not pin it.  A callable that
    cannot be referenced weakly is held as it is (its steps then live in
    its executor's own cache, _fn_step)."""
    try:
        return weakref.ref(fn)
    except TypeError:
        return lambda: fn


def _fn_step(own_cache, key, jax_fn, make):
    """The step of shape `key` bound to `jax_fn`, built by `make(key,
    jax_fn)` at most once per function object and shape: cached under the
    function the user passed, or in `own_cache` (the executor's) for a
    callable that cannot be hashed or referenced weakly."""
    with _FN_STEP_MU:
        try:
            cache = _FN_STEP_CACHE.setdefault(jax_fn.fn, {})
        except TypeError:
            cache = own_cache
        fn = cache.get(key)
        if fn is None:
            fn = cache[key] = make(key, jax_fn)
    return fn


def _bind_udf(jax_fn):
    """What a step keeps of a user's window function, counted as a build:
    its fields and a weak handle on the function."""
    stats_add("udf_step_builds")
    return tuple(jax_fn.fields), _weak_fn(jax_fn.fn)


def _eval_udf(udf, rings, fidx, cap, pad, wrows, wstarts, wlens, wkeys,
              wgwids):
    """The user's window function over (B, pad) gathers of its fields, as a
    tuple of outputs; its operations read ``wf_udf`` in a device trace,
    apart from the append and the gathers around them."""
    fields, fn_ref = udf
    idx = jnp.minimum(
        wstarts[:, None] + jnp.arange(pad, dtype=jnp.int32)[None, :],
        cap - 1)
    mask = jnp.arange(pad, dtype=jnp.int32)[None, :] < wlens[:, None]
    cols = {}
    for f in fields:
        vals = rings[fidx[f]][wrows[:, None], idx]
        cols[f] = jnp.where(mask, vals, 0)
    with jax.named_scope("wf_udf"):
        res = fn_ref()(wkeys, wgwids, cols, mask)
    return res if isinstance(res, tuple) else (res,)


def _make_multi_step(key, jax_fn):
    """Fused multi-field append + eval: one ring per field, reducer stats
    evaluate over their field's ring, and an optional batched JAX window
    function (JaxWindowFunction) reads (B, pad) gathers of every field —
    the device-resident form of the reference's arbitrary device functor
    over whole POD tuples (win_seq_gpu.hpp:54-67): every column crosses
    the wire once, the functor reads HBM."""
    (fields, stats, _fnid, cap, Rb, Bb, KP, wires, accs, pad) = key
    acc_dts = tuple(np.dtype(a) for a in accs)
    fidx = {f: i for i, f in enumerate(fields)}
    udf = None if jax_fn is None else _bind_udf(jax_fn)

    def step(rings, blks, offs, wrows, wstarts, wlens, wkeys, wgwids):
        rings = tuple(_ring_append(r, b, offs, dt)
                      for r, b, dt in zip(rings, blks, acc_dts))
        outs = []
        for op, f in stats:
            outs.append(_ring_eval(op, cap, pad, acc_dts[fidx[f]],
                                   rings[fidx[f]], wrows, wstarts, wlens))
        if udf is not None:
            outs.extend(_eval_udf(udf, rings, fidx, cap, pad, wrows, wstarts,
                                  wlens, wkeys, wgwids))
        return rings, tuple(outs)

    return _named_jit(step, "wf_step_multi")


class MultiFieldResidentExecutor(ResidentWindowExecutor):
    """Resident launch queue with one ring PER FIELD: multi-field
    reducer stats (e.g. sum(a) + max(b)) and arbitrary batched JAX window
    functions evaluate over device-resident archives — rows cross the
    wire once per field instead of once per fire (the restaging path,
    ops/device.py, which mirrors the reference's per-batch H2D memcpy).

    ``stats``: tuple of (op, field) reducer evaluations; ``jax_fn``: an
    optional JaxWindowFunction whose ``fn(keys, gwids, cols, mask)`` runs
    over (B, pad) gathers of its fields.  ``acc_dtypes`` maps each field
    to its ring dtype."""

    def __init__(self, fields, stats=(), jax_fn=None, acc_dtypes=None,
                 device=None, depth: int = 8):
        self.fields = tuple(fields)
        if not self.fields:
            raise ValueError("need at least one ring field")
        self.stats = tuple(stats)
        self.jax_fn = jax_fn
        for op, f in self.stats:
            if op not in self._OPS:
                raise ValueError(f"unsupported resident op {op!r}")
            if f not in self.fields:
                raise ValueError(f"stat field {f!r} not in ring fields")
        if jax_fn is not None:
            for f in jax_fn.fields:
                if f not in self.fields:
                    raise ValueError(f"fn field {f!r} not in ring fields")
        if not self.stats and jax_fn is None:
            raise ValueError("nothing to evaluate")
        self.acc_dtypes = {f: np.dtype(acc_dtypes[f]) for f in self.fields}
        self.device = device or default_device()
        self.depth = depth
        self.cap = 0
        self.KP = 0
        self._rings = None
        self._inflight = deque()
        self._ready = []
        self._svc = deque(maxlen=32)
        self._svc_mean = 0.0
        self.dispatches = 0
        #: the step key's function slot: the function's fields (the function
        #: itself keys _FN_STEP_CACHE), None for a step that binds none
        self._fn_slot = None if jax_fn is None else tuple(jax_fn.fields)
        #: fn-bound steps of a callable that _FN_STEP_CACHE cannot key
        self._step_cache = {}
        #: the smallest bucket of a launch's window count.  A built-in stat
        #: over a few padded windows costs nothing worth a shape; a user's
        #: function is run over every window of the bucket whatever it holds
        #: (one window padded to eight ran an all-pairs function eight
        #: times: 102 ms a launch where 14 do, PERF.md PR 42).  The padded
        #: window length hangs on the same thing: _pad_for
        self._batch_floor = 8 if jax_fn is None else 1

    #: the stats this executor's step evaluates
    _OPS = _REDUCE_OPS
    # single-field plumbing from the base class that does not apply
    op = property(lambda self: tuple(op for op, _f in self.stats))
    single = False

    def reset(self, n_keys: int, cap: int):
        self.KP = _bucket(max(n_keys, 1))
        self.cap = _bucket(max(cap, 16))
        self._rings = None

    def _rings_arr(self):
        if self._rings is None:
            self._rings = tuple(
                jax.device_put(
                    jnp.zeros((self.KP, self.cap),
                              dtype=self.acc_dtypes[f]), self.device)
                for f in self.fields)
        return self._rings

    def _rings_tuple(self):
        return self._rings

    def _rings_assign(self, rings):
        self._rings = rings

    def narrow_for(self, field, vals: np.ndarray) -> np.dtype:
        """Per-field wire narrowing (same ladder as the base class but
        bounded by that field's ring dtype)."""
        acc = self.acc_dtypes[field]
        wide = acc.itemsize >= 8
        if len(vals) and vals.dtype.kind == "f" and acc.kind != "f":
            raise ValueError(
                f"float column {field!r} headed into a {acc} ring would "
                "silently truncate — declare a float ring dtype "
                f"(JaxWindowFunction(field_dtypes={{{field!r}: "
                "np.float32}}))")
        if acc.kind == "f":
            return np.dtype(np.float64 if wide else np.float32)
        if not len(vals):
            return np.dtype(np.int8)
        lo, hi = int(vals.min()), int(vals.max())
        ladder = (np.int8, np.int16, np.int32, np.int64) if wide else \
                 (np.int8, np.int16, np.int32)
        for dt in ladder:
            info = np.iinfo(dt)
            if info.min <= lo and hi <= info.max:
                return np.dtype(dt)
        return np.dtype(ladder[-1])

    def _pad_for(self, wlens) -> int:
        """The padded window length of a launch, part of its step's shape:
        the longest window's bucket.  A built-in stat's is the power of two
        (a padded cell is a masked lane of a cheap gather-reduce, and finer
        buckets would multiply its shapes), 0 where every stat is a prefix
        sum's; a user's function pays for every cell, an all-pairs one for
        their square, so its windows go up `_bucket_fine`'s ladder (131,072
        cells for a window of 102,400 ran 1.64 times the pair tests needed,
        PERF.md PR 43)."""
        longest = int(wlens.max()) if len(wlens) else 1
        if self.jax_fn is not None:
            return _bucket_fine(longest)
        if any(op != "sum" for op, _f in self.stats):
            return _bucket(longest)
        return 0

    def launch(self, meta, blks: dict, offs: np.ndarray,
               wrows: np.ndarray, wstarts: np.ndarray, wlens: np.ndarray,
               wkeys: np.ndarray = None, wgwids: np.ndarray = None,
               tag=_NO_TAG):
        """One fused dispatch: per-field rectangles `blks[f]` (K, R) append
        at `offs`, then every stat / the JAX fn evaluates the described
        windows.  `wkeys`/`wgwids` are required when a JAX fn is bound."""
        K, R = next(iter(blks.values())).shape
        if K > self.KP:
            raise ValueError("rectangle exceeds ring rows; reset() first")
        B = len(wstarts)
        Rb = _bucket(max(R, 1))
        Bb = _bucket(max(B, 1), lo=self._batch_floor)
        _check_ring_overflow(offs, Rb, self.cap)
        pad = self._pad_for(wlens)
        wires = tuple(blks[f].dtype.str for f in self.fields)
        key = (self.fields, self.stats, self._fn_slot, self.cap, Rb, Bb,
               self.KP, wires,
               tuple(self.acc_dtypes[f].str for f in self.fields), pad)
        # stat-only steps share the process-wide cache like the base class;
        # a step bound to a user's function is cached under that function
        if self.jax_fn is None:
            fn = _STEP_CACHE.get(key)
            if fn is None:
                fn = _STEP_CACHE[key] = _make_multi_step(key, None)
        else:
            fn = _fn_step(self._step_cache, key, self.jax_fn,
                          _make_multi_step)
        with profile.span("device_put", *tag):
            blkps = tuple(
                (blks[f] if blks[f].shape == (self.KP, Rb)
                 else _pad2(blks[f], self.KP, Rb)) for f in self.fields)
            args = jax.device_put(
                (blkps, _pad1(offs, self.KP), _pad1(wrows, Bb),
                 _pad1(wstarts, Bb), _pad1(wlens, Bb),
                 _pad1(wkeys if wkeys is not None else np.zeros(0), Bb,
                       dtype=np.int64),
                 _pad1(wgwids if wgwids is not None else np.zeros(0), Bb,
                       dtype=np.int64)),
                self.device)
        for f in self.fields:
            profile.add("bytes_shipped", blks[f].nbytes)
            profile.add("rows_shipped", blks[f].size)
        profile.add("windows", B)
        if self.jax_fn is not None:
            self._count_udf(B, wlens, Bb * pad)
        with profile.span("dispatch", *tag) as sp:
            self._rings, out = fn(self._rings_arr(), *args)
            for o in out:
                o.copy_to_host_async()
        self._dispatched(meta, B, out, sp, tag)

    @staticmethod
    def _count_udf(B, wlens, cells):
        """One launch of a user's window function: its windows, the rows
        they hold, and the cells the library padded them to (`cells`: every
        window of the bucketed batch at the longest length's step of
        `_bucket_fine`'s ladder)."""
        profile.add("udf_windows", B)
        profile.add("udf_rows", int(np.sum(wlens, dtype=np.int64)))
        profile.add("udf_cells", cells)


# -- the arg-extremum family ---------------------------------------------------
# ops/functions.ArgReducer on the native resident core: windows of 10^7 rows
# of ONE ring row (a key-less stream split over a Win_MapReduce).  The
# (Bb, pad) gather of _ring_eval would materialise Bb x bucket(longest
# window) cells to reduce one row; this family walks each window in blocks of
# the ring row instead, O(window) cells read once whatever Bb and cap, and
# returns where the extremum first sits.

_ARG_OPS = ("argmax", "argmin")
#: cells of a ring row one step of the blockwise evaluation reads
ARGEXT_BLOCK = 1 << 20


def _ring_extremum(op, cap, eb, acc_dt, ring, rows, starts, lens):
    """Per described window: the extremum of `op` (``max``/``min``), the
    window-relative index of its first occurrence and how many cells hold
    it.  Each window walks its own cells of its ring row in blocks of `eb`
    (a ``fori_loop`` over the blocks it spans inside a ``lax.map`` over the
    windows): an empty window, padding included, reads nothing."""
    ident = jnp.asarray(_identity(op, acc_dt), dtype=acc_dt)
    red = jnp.max if op == "max" else jnp.min
    iota = jnp.arange(eb, dtype=jnp.int32)

    def one(w):
        r, s, l = w
        e = s + l

        def body(b, st):
            ext, first, n = st
            p0 = b * eb
            seg = lax.dynamic_slice(ring, (r, p0), (1, eb))[0]
            pos = p0 + iota
            inside = (pos >= s) & (pos < e)
            bext = red(jnp.where(inside, seg, ident))
            hit = inside & (seg == bext)
            bfirst = jnp.min(jnp.where(hit, pos, cap))
            bn = jnp.sum(hit, dtype=jnp.int32)
            better = bext > ext if op == "max" else bext < ext
            same = bext == ext
            return (jnp.where(better, bext, ext),
                    jnp.where(better, bfirst,
                              jnp.where(same, jnp.minimum(first, bfirst),
                                        first)),
                    jnp.where(better, bn, jnp.where(same, n + bn, n)))

        ext, first, n = lax.fori_loop(
            s // eb, jnp.where(l > 0, (e + eb - 1) // eb, s // eb), body,
            (ident, jnp.int32(cap), jnp.int32(0)))
        return ext, jnp.where(n > 0, first - s, 0), n

    return lax.map(one, (rows, starts, lens))


def _ring_compact(ring, shifts):
    """Slide every ring row left by its shift (the core dropped that many
    dead cells from the row's head); rows that do not move are not read."""
    def slide(r):
        return jax.vmap(lambda row, sh: lax.dynamic_slice(
            jnp.concatenate([row, jnp.zeros_like(row)]), (sh,),
            (row.shape[0],)))(r, shifts)
    return lax.cond(jnp.any(shifts != 0), slide, lambda r: r, ring)


def _make_argext_step(key):
    """Fused compact + append + blockwise evaluation: one ring per field,
    donated so that the append is in place (a ring of 2^26 cells is not
    copied per launch); ``argmax``/``argmin`` stats return (extremum, first
    index, count of cells at the extremum), ``max``/``min`` the extremum by
    the same walk, ``sum``/``prod`` as :func:`_ring_eval`."""
    (_tag, fields, stats, cap, Rb, Bb, KP, wires, accs, pad, eb) = key
    acc_dts = tuple(np.dtype(a) for a in accs)
    fidx = {f: i for i, f in enumerate(fields)}

    def step(rings, blks, offs, shifts, wrows, wstarts, wlens):
        rings = tuple(_ring_append(_ring_compact(r, shifts), b, offs, dt)
                      for r, b, dt in zip(rings, blks, acc_dts))
        outs = []
        for op, f in stats:
            ring, dt = rings[fidx[f]], acc_dts[fidx[f]]
            if op in _ARG_OPS:
                outs.extend(_ring_extremum(op[3:], cap, eb, dt, ring, wrows,
                                           wstarts, wlens))
            elif op in ("max", "min"):
                outs.append(_ring_extremum(op, cap, eb, dt, ring, wrows,
                                           wstarts, wlens)[0])
            else:
                outs.append(_ring_eval(op, cap, pad, dt, ring, wrows,
                                       wstarts, wlens))
        return rings, tuple(outs)

    return _named_jit(step, "wf_step_argext", donate_argnums=0)


class ArgExtResidentExecutor(MultiFieldResidentExecutor):
    """Resident launch queue of the arg-extremum family
    (``jit_wf_step_argext``): per-field rings whose rows are bucketed from 1
    (a key-less stream is one row, not eight), donated to every step,
    compacted and grown on the device (:meth:`launch`'s ``shifts``,
    :meth:`grow`) instead of re-shipped from the host, and evaluated
    blockwise (:func:`_ring_extremum`).  The native resident core drives it
    (patterns/native_core.py); it serves no mesh and no JAX window
    function."""

    _OPS = _REDUCE_OPS + _ARG_OPS

    def __init__(self, fields, stats, acc_dtypes, device=None,
                 depth: int = 8):
        super().__init__(fields, stats=stats, acc_dtypes=acc_dtypes,
                         device=device, depth=depth)
        self.eval_block = ARGEXT_BLOCK

    def reset(self, n_keys: int, cap: int):
        self.KP = _bucket(max(n_keys, 1), lo=1)
        self.cap = _bucket(max(cap, 16))
        self._rings = None

    def grow(self, cap: int):
        """Widen every ring to `cap` cells a row on the device, contents
        kept (the core asks when its live rows outgrow half the ring)."""
        if cap <= self.cap:
            return
        old = self._rings_arr()
        self.cap = cap
        self._rings = None
        # fresh rings as :meth:`_rings_arr` makes them, the old contents
        # written at the front
        self._rings = tuple(lax.dynamic_update_slice(z, r, (0, 0))
                            for z, r in zip(self._rings_arr(), old))

    def launch(self, meta, blks: dict, offs: np.ndarray,
               wrows: np.ndarray, wstarts: np.ndarray, wlens: np.ndarray,
               shifts: np.ndarray = None, tag=_NO_TAG):
        """One fused dispatch: slide the ring rows left by `shifts` (None:
        nothing moves), append the per-field rectangles at `offs` (already
        in the slid coordinates, like the window descriptors), evaluate."""
        K, R = next(iter(blks.values())).shape
        if K > self.KP:
            raise ValueError("rectangle exceeds ring rows; reset() first")
        B = len(wstarts)
        Rb = _bucket(max(R, 1))
        Bb = _bucket(max(B, 1))
        _check_ring_overflow(offs, Rb, self.cap)
        pad = (_bucket(int(wlens.max()) if B else 1)
               if any(op == "prod" for op, _f in self.stats) else 0)
        eb = min(self.eval_block, self.cap)
        key = ("argext", self.fields, self.stats, self.cap, Rb, Bb, self.KP,
               tuple(blks[f].dtype.str for f in self.fields),
               tuple(self.acc_dtypes[f].str for f in self.fields), pad, eb)
        fn = _STEP_CACHE.get(key)
        if fn is None:
            fn = _STEP_CACHE[key] = _make_argext_step(key)
        with profile.span("device_put", *tag):
            blkps = tuple(
                (blks[f] if blks[f].shape == (self.KP, Rb)
                 else _pad2(blks[f], self.KP, Rb)) for f in self.fields)
            args = jax.device_put(
                (blkps, _pad1(offs, self.KP),
                 _pad1(shifts if shifts is not None else (), self.KP),
                 _pad1(wrows, Bb), _pad1(wstarts, Bb), _pad1(wlens, Bb)),
                self.device)
        for f in self.fields:
            profile.add("bytes_shipped", blks[f].nbytes)
            profile.add("rows_shipped", blks[f].size)
        profile.add("windows", B)
        if B:
            profile.add("eval_windows", B)
            profile.add("eval_rows", int(np.sum(wlens, dtype=np.int64)))
        with profile.span("dispatch", *tag) as sp:
            self._rings, out = fn(self._rings_arr(), *args)
            for o in out:
                o.copy_to_host_async()
        self._dispatched(meta, B, out, sp, tag)


def _make_mesh_multi_step(key, jax_fn):
    """Sharded fused multi-field append+eval: shard_map over the key-group
    axis of the per-field rings — each device appends its row block of
    EVERY field's ring and evaluates its own windows' stats/fn (windows
    are row-local, so the program has no collectives; the multi-chip form
    of the whole-tuple functor contract, win_seq_gpu.hpp:54-67 x SURVEY
    §2.8)."""
    (_tag, fields, stats, _fnid, cap, Rb, Bs, KP, wires, accs, pad, mesh,
     axis) = key
    acc_dts = tuple(np.dtype(a) for a in accs)
    fidx = {f: i for i, f in enumerate(fields)}
    udf = None if jax_fn is None else _bind_udf(jax_fn)
    from jax.sharding import PartitionSpec as P

    def local(rings, blks, offs, lrows, lstarts, llens, lkeys, lgwids):
        # per-shard views: rings/blks (rps, .) per field, offs (rps,),
        # descriptors (1, Bs) — this shard's windows, host pre-grouped
        rings = tuple(_ring_append(r, b, offs, dt)
                      for r, b, dt in zip(rings, blks, acc_dts))
        wrows, wstarts, wlens = lrows[0], lstarts[0], llens[0]
        outs = []
        for op, f in stats:
            outs.append(_ring_eval(op, cap, pad, acc_dts[fidx[f]],
                                   rings[fidx[f]], wrows, wstarts, wlens))
        if udf is not None:
            outs.extend(_eval_udf(udf, rings, fidx, cap, pad, wrows, wstarts,
                                  wlens, lkeys[0], lgwids[0]))
        outs = tuple(o[None, :] for o in outs)
        return rings, outs

    n_f = len(fields)
    mapped = jax.shard_map(
        local, mesh=mesh,
        in_specs=((P(axis, None),) * n_f, (P(axis, None),) * n_f,
                  P(axis), P(axis, None), P(axis, None), P(axis, None),
                  P(axis, None), P(axis, None)),
        out_specs=((P(axis, None),) * n_f, P(axis, None)))
    return _named_jit(mapped, "wf_step_multi_mesh")


class MeshMultiFieldResidentExecutor(MultiFieldResidentExecutor):
    """Multi-field resident rings sharded ``P(kf, None)`` over a mesh:
    the per-field-ring generalisation of :class:`MeshResidentExecutor` —
    arbitrary multi-stat reducers and batched JAX window functions run
    over key-group-sharded archives, one SPMD dispatch for every group
    (the general whole-tuple functor contract, win_seq_gpu.hpp:54-67,
    distributed over the ICI mesh)."""

    def __init__(self, fields, stats=(), jax_fn=None, acc_dtypes=None,
                 mesh=None, axis: str = "kf", depth: int = 8):
        if mesh is None or axis not in mesh.shape:
            raise ValueError(f"need a mesh with axis {axis!r}")
        super().__init__(fields, stats=stats, jax_fn=jax_fn,
                         acc_dtypes=acc_dtypes,
                         device=mesh.devices.flat[0], depth=depth)
        self.mesh = mesh
        self.axis = axis
        self.n_shards = int(mesh.shape[axis])

    def _sharding(self, *spec):
        from jax.sharding import NamedSharding, PartitionSpec as P
        return NamedSharding(self.mesh, P(*spec))

    def _ring_placement(self):
        return self._sharding(self.axis, None)

    def reset(self, n_keys: int, cap: int):
        S = self.n_shards
        rows_per_shard = _bucket(max(-(-max(n_keys, 1) // S), 1))
        self.KP = S * rows_per_shard
        self.cap = _bucket(max(cap, 16))
        self._rings = None

    def _rings_arr(self):
        if self._rings is None:
            self._rings = tuple(
                jax.device_put(
                    jnp.zeros((self.KP, self.cap),
                              dtype=self.acc_dtypes[f]),
                    self._sharding(self.axis, None))
                for f in self.fields)
        return self._rings

    def launch(self, meta, blks: dict, offs: np.ndarray,
               wrows: np.ndarray, wstarts: np.ndarray, wlens: np.ndarray,
               wkeys: np.ndarray = None, wgwids: np.ndarray = None,
               tag=_NO_TAG):
        S = self.n_shards
        K, R = next(iter(blks.values())).shape
        if K > self.KP:
            raise ValueError("rectangle exceeds ring rows; reset() first")
        rps = self.KP // S
        B = len(wstarts)
        wrows = np.asarray(wrows, dtype=np.int64)
        # stride dense key rows over shards (MeshResidentExecutor.launch)
        shard = wrows % S
        local = wrows // S
        slots = np.zeros(B, dtype=np.int64)
        maxc = 0
        for s in range(S):
            m = shard == s
            c = int(m.sum())
            slots[m] = np.arange(c)
            maxc = max(maxc, c)
        Bs = _bucket(max(maxc, 1), lo=self._batch_floor)
        lrows = np.zeros((S, Bs), dtype=np.int32)
        lstarts = np.zeros((S, Bs), dtype=np.int32)
        llens = np.zeros((S, Bs), dtype=np.int32)
        lkeys = np.zeros((S, Bs), dtype=np.int64)
        lgwids = np.zeros((S, Bs), dtype=np.int64)
        if B:
            lrows[shard, slots] = local.astype(np.int32)
            lstarts[shard, slots] = wstarts
            llens[shard, slots] = wlens
            # the caller sends empty header columns when no fn is bound
            if wkeys is not None and len(wkeys) == B:
                lkeys[shard, slots] = wkeys
            if wgwids is not None and len(wgwids) == B:
                lgwids[shard, slots] = wgwids
        Rb = _bucket(max(R, 1))
        _check_ring_overflow(offs, Rb, self.cap)
        pad = self._pad_for(wlens)
        wires = tuple(blks[f].dtype.str for f in self.fields)
        key = ("mesh-multi", self.fields, self.stats, self._fn_slot,
               self.cap, Rb, Bs, self.KP, wires,
               tuple(self.acc_dtypes[f].str for f in self.fields), pad,
               self.mesh, self.axis)
        if self.jax_fn is None:
            fn = _STEP_CACHE.get(key)
            if fn is None:
                fn = _STEP_CACHE[key] = _make_mesh_multi_step(key, None)
        else:
            fn = _fn_step(self._step_cache, key, self.jax_fn,
                          _make_mesh_multi_step)
        # shard-major physical scatter (MeshResidentExecutor.launch)
        rows = np.arange(K)
        prow = (rows % S) * rps + rows // S
        offsp = np.zeros(self.KP, dtype=np.int32)
        offsp[prow] = offs
        blkps = []
        with profile.span("device_put", *tag):
            for f in self.fields:
                bp = np.zeros((self.KP, Rb), dtype=blks[f].dtype)
                bp[prow, :R] = blks[f]
                blkps.append(jax.device_put(bp, self._sharding(self.axis,
                                                               None)))
            s2 = self._sharding(self.axis, None)
            args = (tuple(blkps),
                    jax.device_put(offsp, self._sharding(self.axis)),
                    jax.device_put(lrows, s2), jax.device_put(lstarts, s2),
                    jax.device_put(llens, s2), jax.device_put(lkeys, s2),
                    jax.device_put(lgwids, s2))
        for f in self.fields:
            profile.add("bytes_shipped", blks[f].nbytes)
            profile.add("rows_shipped", blks[f].size)
        profile.add("windows", B)
        if self.jax_fn is not None:
            self._count_udf(B, wlens, S * Bs * pad)
        with profile.span("dispatch", *tag) as sp:
            self._rings, out = fn(self._rings_arr(), *args)
            for o in out:
                o.copy_to_host_async()
        self._dispatched(meta, (shard, slots), out, sp, tag)


class MeshResidentExecutor(ResidentWindowExecutor):
    """Resident ring sharded ``P(kf, None)`` over a ``jax.sharding.Mesh``:
    dense-key ring rows are block-distributed over the mesh's key-group
    axis, so ONE fused append+eval dispatch serves every key group — each
    chip holds its groups' archives in its own HBM and evaluates its own
    windows (no collectives; the kf axis is embarrassingly parallel,
    parallel/mesh.py).  This is the multi-chip form of the reference's
    per-worker GPU ownership (win_farm_gpu.hpp:132-168) with the farm
    collapsed into one SPMD program."""

    def __init__(self, op: str, mesh, axis: str = "kf", depth: int = 8,
                 acc_dtype=np.int32):
        if axis not in mesh.shape:
            raise ValueError(f"mesh has no axis {axis!r}: {mesh.shape}")
        super().__init__(op, device=mesh.devices.flat[0], depth=depth,
                         acc_dtype=acc_dtype)
        self.mesh = mesh
        self.axis = axis
        self.n_shards = int(mesh.shape[axis])

    def _sharding(self, *spec):
        from jax.sharding import NamedSharding, PartitionSpec as P
        return NamedSharding(self.mesh, P(*spec))

    def _ring_placement(self):
        return self._sharding(self.axis, None)

    def reset(self, n_keys: int, cap: int):
        S = self.n_shards
        rows_per_shard = _bucket(max(-(-max(n_keys, 1) // S), 1))
        self.KP = S * rows_per_shard
        self.cap = _bucket(max(cap, 16))
        self._ring = None

    def _ring_arr(self):
        if self._ring is None:
            self._ring = jax.device_put(
                jnp.zeros((self.KP, self.cap), dtype=self.acc_dtype),
                self._sharding(self.axis, None))
        return self._ring

    def launch(self, meta, blk: np.ndarray, offs: np.ndarray,
               wrows: np.ndarray, wstarts: np.ndarray, wlens: np.ndarray,
               tag=_NO_TAG):
        S = self.n_shards
        K, R = blk.shape
        if K > self.KP:
            raise ValueError("rectangle exceeds ring rows; reset() first")
        rps = self.KP // S
        B = len(wstarts)
        wrows = np.asarray(wrows, dtype=np.int64)
        # STRIDE dense key rows over shards (row r -> shard r % S, local
        # slot r // S): the host assigns rows in key-arrival order, so a
        # block mapping would concentrate all live keys on the low shards
        # while the padded tail idles — striding balances any K
        shard = wrows % S
        local = wrows // S
        # per-shard slot assignment, preserving original order per shard
        slots = np.zeros(B, dtype=np.int64)
        maxc = 0
        for s in range(S):
            m = shard == s
            c = int(m.sum())
            slots[m] = np.arange(c)
            maxc = max(maxc, c)
        Bs = _bucket(max(maxc, 1))
        lrows = np.zeros((S, Bs), dtype=np.int32)
        lstarts = np.zeros((S, Bs), dtype=np.int32)
        llens = np.zeros((S, Bs), dtype=np.int32)
        if B:
            lrows[shard, slots] = local.astype(np.int32)
            lstarts[shard, slots] = wstarts
            llens[shard, slots] = wlens
        Rb = _bucket(max(R, 1))
        _check_ring_overflow(offs, Rb, self.cap)
        pad = (_bucket(int(wlens.max()) if B else 1)
               if any(o != "sum" for o in self.ops) else 0)
        key = ("mesh", self.ops, self.cap, Rb, Bs, self.KP, blk.dtype.str,
               self.acc_dtype.str, pad, self.mesh, self.axis)
        fn = _STEP_CACHE.get(key)
        if fn is None:
            fn = _STEP_CACHE[key] = _make_mesh_step(key)
        # scatter the rectangle so dense row r lands at physical ring row
        # (r % S) * rps + r // S — shard-major, matching the window mapping
        rows = np.arange(K)
        prow = (rows % S) * rps + rows // S
        blkp = np.zeros((self.KP, Rb), dtype=blk.dtype)
        blkp[prow, :R] = blk
        offsp = np.zeros(self.KP, dtype=np.int32)
        offsp[prow] = offs
        with profile.span("device_put", *tag):
            args = (jax.device_put(blkp, self._sharding(self.axis, None)),
                    jax.device_put(offsp, self._sharding(self.axis)),
                    jax.device_put(lrows, self._sharding(self.axis, None)),
                    jax.device_put(lstarts, self._sharding(self.axis, None)),
                    jax.device_put(llens, self._sharding(self.axis, None)))
        with profile.span("dispatch", *tag) as sp:
            self._ring, out = fn(self._ring_arr(), *args)
            for o in (out if isinstance(out, tuple) else (out,)):
                o.copy_to_host_async()
        # harvest indexes the (S, Bs) result back to flat window order
        self._dispatched(meta, (shard, slots), out, sp, tag)

    def launch_regular(self, meta, blk: np.ndarray, offs: np.ndarray,
                       rcount: np.ndarray, rstart0: np.ndarray,
                       rlen: np.ndarray, slide: int, wrows: np.ndarray,
                       widx: np.ndarray, cmax: int = 0, tag=_NO_TAG):
        """Regular-descriptor dispatch on the sharded ring: the per-key
        (count, start0, len) scalars shard with their rows, and each device
        expands its own arithmetic window sequences — the native core's
        wire compression composes with mesh execution (r2 weak #3)."""
        if not (self.single and self.op == "sum"):
            raise ValueError("regular descriptors implemented for "
                             "single-stat sum")
        S = self.n_shards
        K, R = blk.shape
        if K > self.KP:
            raise ValueError("rectangle exceeds ring rows; reset() first")
        rps = self.KP // S
        Rb = _bucket(max(R, 1))
        C = _bucket(int(cmax) if cmax else
                    (int(rcount.max()) if len(rcount) else 1))
        _check_ring_overflow(offs, Rb, self.cap)
        key = ("mesh-reg", self.op, self.cap, Rb, self.KP, C, blk.dtype.str,
               self.acc_dtype.str, int(slide), self.mesh, self.axis)
        fn = _STEP_CACHE.get(key)
        if fn is None:
            fn = _STEP_CACHE[key] = _make_mesh_regular_step(key)
        # strided physical scatter, same mapping as launch()
        rows = np.arange(K)
        prow = (rows % S) * rps + rows // S
        blkp = np.zeros((self.KP, Rb), dtype=blk.dtype)
        blkp[prow, :R] = blk[:, :R]
        def scat(a, dtype=np.int32):
            out = np.zeros(self.KP, dtype=dtype)
            out[prow] = a[:K]
            return out
        with profile.span("device_put", *tag):
            args = (jax.device_put(blkp, self._sharding(self.axis, None)),
                    jax.device_put(scat(offs), self._sharding(self.axis)),
                    jax.device_put(scat(rcount), self._sharding(self.axis)),
                    jax.device_put(scat(rstart0), self._sharding(self.axis)),
                    jax.device_put(scat(rlen), self._sharding(self.axis)))
        with profile.span("dispatch", *tag) as sp:
            self._ring, out = fn(self._ring_arr(), *args)
            out.copy_to_host_async()
        wr = np.asarray(wrows, dtype=np.int64)
        sel = ((wr % S) * rps + wr // S, np.asarray(widx))
        self._dispatched(meta, sel, out, sp, tag)


def make_executor(family: str, fields, stats, acc_dtypes, *, jax_fn=None,
                  mesh=None, device=None, depth: int = 8):
    """The resident executor of one step family — the one place that names
    the executor classes.  ``family`` is ``"regular"`` (one ring, every op
    of ``stats`` over it), ``"multi"`` (a ring per field; the only family
    that takes a ``jax_fn``) or ``"argext"``; ``stats`` are (op, field)
    pairs and ``acc_dtypes`` maps each field to its ring dtype.  With
    ``mesh`` the rings shard ``P(kf, None)`` over it, else they live on
    ``device``."""
    if family == "argext":
        return ArgExtResidentExecutor(fields, stats, acc_dtypes,
                                      device=device, depth=depth)
    if family == "multi":
        if mesh is not None:
            return MeshMultiFieldResidentExecutor(
                fields, stats=stats, jax_fn=jax_fn, acc_dtypes=acc_dtypes,
                mesh=mesh, depth=depth)
        return MultiFieldResidentExecutor(
            fields, stats=stats, jax_fn=jax_fn, acc_dtypes=acc_dtypes,
            device=device, depth=depth)
    (field,) = fields
    ops = tuple(op for op, _f in stats)
    op = ops[0] if len(ops) == 1 else ops
    if mesh is not None:
        return MeshResidentExecutor(op, mesh, depth=depth,
                                    acc_dtype=acc_dtypes[field])
    return ResidentWindowExecutor(op, device=device, depth=depth,
                                  acc_dtype=acc_dtypes[field])


def prewarm_regular_ladder(mults=(2, 4, 8, 16), devices=None,
                           max_cells=1 << 24) -> int:
    """Compile the coalesced-shape siblings of every step (regular,
    irregular, mesh) already compiled in this process.

    Deep launch coalescing dispatches merged shapes on the {2x, 4x, ...}
    buddy ladder — diagonal (Rb*m, B*m) siblings for irregular steps, the
    lower triangle {(Rb*m, C*b), b <= m} for regular steps (try_merge
    admits window-bucket growth at most proportional to row-bucket
    growth) — only when launches queue up behind a slow service, exactly
    when a cold mid-run compile hurts most.  A benchmark calls this once
    after its warmup run: whatever regular buckets the warmup compiled,
    their ladder siblings compile now, deterministically, regardless of
    the launch service the warmup happened to see.  ``devices`` should
    list every device the run's executors own (jit executables cache per
    placement; a farm worker on another chip would otherwise cold-compile
    its first merged shape) — default is device 0 only.  Returns the
    number of steps compiled."""
    devices = list(devices) if devices else [default_device()]
    warmed = 0
    for key in list(_STEP_CACHE):
        if key in _PREWARMED:
            # a prewarmed sibling never seeds further ladders: the buddy
            # multiplicity caps at 16x of a NATURAL launch shape, so
            # ladders-of-ladders are undispatchable (and repeat calls
            # must be no-ops)
            continue
        tag = key[0] if isinstance(key, tuple) and key else None
        if tag == "reg":
            _t, op, cap, Rb, KP, C, blk_dt, acc_dt, slide = key
            mesh = axis = None
        elif tag == "mesh-reg":
            (_t, op, cap, Rb, KP, C, blk_dt, acc_dt, slide, mesh,
             axis) = key
        elif isinstance(tag, tuple) and len(key) == 8:
            # plain (irregular-descriptor) step: TB windows and non-sum
            # ops merge on explicit descriptors, so their ladder siblings
            # double both the rectangle AND the window-count bucket.
            # (multi-field keys are also tuple-tagged but 10-long — their
            # executor is Python-core only, which never coalesces)
            _ops, cap, Rb, Bb, KP, blk_dt, acc_dt, pad = key
            mesh = axis = None
        elif tag == "mesh":
            # mesh irregular step: the coalescer merges irregular launches
            # on the mesh-backed native path too (non-sum ops, TB windows),
            # so merged (Rb*m, Bs*m) diagonal siblings must be warm as well
            # (ADVICE r3).  The per-shard window bucket Bs tracks the total
            # window count's bucket in the common case (strided shard
            # assignment); the diagonal ladder covers exactly those.
            (_t, ops_m, cap, Rb, Bb, KP, blk_dt, acc_dt, pad, mesh,
             axis) = key
        else:
            continue
        for m in mults:
            # a real merge can never exceed the ring (try_merge's offset
            # guard bounds bucket(newR) by cap) ...
            if Rb * m > cap:
                continue
            # ... and its area guard counts LIVE keys (K2 * bucket(newR)
            # <= max_cells, wf_native.cpp:try_merge); the smallest live K
            # a KP-row launch can carry is KP//2 + 1 (bucket property), so
            # skip only shapes NO admissible merge could produce — a
            # padded-KP guard here would refuse shapes the coalescer then
            # builds and compiles cold mid-run
            if (KP // 2 + 1) * Rb * m > max_cells:
                continue
            if isinstance(tag, tuple):
                sks = [(tag, cap, Rb * m, Bb * m, KP, blk_dt, acc_dt, pad)]
            elif tag == "mesh":
                # the mesh dispatch key's window bucket Bs is PER-SHARD
                # (bucket of the fullest shard's window count,
                # MeshResidentExecutor.launch) while try_merge guards the
                # TOTAL window bucket — clamping decouples them (merged
                # per-shard counts can sit under the lo=8 clamp while rows
                # double), so merged mesh shapes live on the same lower
                # triangle as regular ones: warm {(Rb*m, Bs*b), b <= m}
                sks = []
                b = 1
                while b <= m:
                    sks.append(("mesh", ops_m, cap, Rb * m, Bb * b, KP,
                                blk_dt, acc_dt, pad, mesh, axis))
                    b *= 2
            else:
                # regular merges live on the LOWER TRIANGLE {(Rb*a, C*b),
                # b <= a}: small per-key window counts can clamp the C
                # bucket while rows double (try_merge admits rc <= rr), so
                # the diagonal sibling alone would leave e.g. (2*Rb, C)
                # cold exactly when the coalescer builds it mid-stall
                # (ADVICE r3)
                sks = []
                b = 1
                while b <= m:
                    if mesh is None:
                        sks.append(("reg", op, cap, Rb * m, KP, C * b,
                                    blk_dt, acc_dt, slide))
                    else:
                        sks.append(("mesh-reg", op, cap, Rb * m, KP, C * b,
                                    blk_dt, acc_dt, slide, mesh, axis))
                    b *= 2
            todo = [sk for sk in sks if sk not in _STEP_CACHE]
            if not todo:
                continue
            # the warm inputs depend only on (family, m), never on the
            # triangle's C value (it shapes the OUTPUT only) — allocate
            # them once per placement and reuse across siblings (a ring is
            # up to 128 MB; re-shipping it per sibling would stretch the
            # warmup window for nothing)
            if mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec as P
                s2 = NamedSharding(mesh, P(axis, None))
                s1 = NamedSharding(mesh, P(axis))
                placements = [(s2, s1)]
            else:
                placements = [(dev, dev) for dev in devices]
            bases = []
            for p2, p1 in placements:
                ring = jax.device_put(
                    jnp.zeros((KP, cap), dtype=np.dtype(acc_dt)), p2)
                blk = jax.device_put(
                    jnp.zeros((KP, Rb * m), dtype=np.dtype(blk_dt)), p2)
                zk = jax.device_put(jnp.zeros(KP, dtype=np.int32), p1)
                bases.append((p2, p1, ring, blk, zk))
            for sk in todo:
                # cache only AFTER the warm dispatch succeeds: a transient
                # device error mid-warm must leave the key retryable, not
                # "warm" with a cold executable behind it
                if tag == "mesh":
                    fn = _make_mesh_step(sk)
                elif isinstance(tag, tuple):
                    fn = _make_step(sk)
                elif mesh is None:
                    fn = _make_regular_step(sk)
                else:
                    fn = _make_mesh_regular_step(sk)
                for p2, p1, ring, blk, zk in bases:
                    # the window-descriptor vectors are the one input whose
                    # shape varies across mesh/plain irregular siblings
                    # (sk[4] / sk[3] is that sibling's Bs); regular steps
                    # take per-key scalars only
                    if tag == "mesh":
                        S = int(mesh.shape[axis])
                        zb = jax.device_put(
                            jnp.zeros((S, sk[4]), dtype=np.int32), p2)
                        args = (ring, blk, zk, zb, zb, zb)
                    elif isinstance(tag, tuple):
                        zb = jax.device_put(
                            jnp.zeros(sk[3], dtype=np.int32), p1)
                        args = (ring, blk, zk, zb, zb, zb)
                    else:
                        args = (ring, blk, zk, zk, zk, zk)
                    _ring2, out = fn(*args)
                    jax.block_until_ready(out)
                _STEP_CACHE[sk] = fn
                _PREWARMED.add(sk)
                warmed += 1
    return warmed
