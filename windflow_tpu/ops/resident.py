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
from typing import NamedTuple

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


def _zeros(shape, dtype, where):
    """Zeros on the device: `where` a device or a sharding."""
    return jax.device_put(jnp.zeros(shape, dtype=dtype), where)


def _pad2(a, rows, cols):
    out = np.zeros((rows, cols), dtype=a.dtype)
    out[:a.shape[0], :a.shape[1]] = a
    return out


def _pad1(a, size, dtype=np.int32):
    out = np.zeros(size, dtype=dtype)
    out[:len(a)] = a
    return out


def _wire_dtype(vals, acc, as_float: bool) -> np.dtype:
    """The narrowest wire dtype that holds `vals` exactly under a ring of
    dtype `acc`: int8/int16/int32, int64 too under a 64-bit ring; a float
    column (`as_float`) ships in the ring's precision."""
    wide = acc.itemsize >= 8
    if as_float:
        return np.dtype(np.float64 if wide else np.float32)
    if not len(vals):
        return np.dtype(np.int8)
    lo, hi = int(vals.min()), int(vals.max())
    ladder = (np.int8, np.int16, np.int32) + ((np.int64,) if wide else ())
    for dt in ladder:
        info = np.iinfo(dt)
        if info.min <= lo and hi <= info.max:
            return np.dtype(dt)
    return np.dtype(ladder[-1])  # wraps; the core warned at
    # construction when the result dtype exceeds the accumulate dtype


def _check_ring_overflow(offs, Rb, cap):
    """dynamic_update_slice clamps the start, which would silently
    overwrite live cells near the ring end — the host core's rebase
    invariant must prevent ever getting here."""
    if len(offs) and int(offs.max()) + Rb > cap:
        raise ValueError(
            f"ring overflow: offset {int(offs.max())} + {Rb} > {cap}")


class StepKey(NamedTuple):
    """The shape of one compiled step: what ``_STEP_CACHE`` and the dicts of
    ``_FN_STEP_CACHE`` are keyed by.  A family sets the fields it has and
    leaves the others at their defaults."""
    #: ``regular``, ``append_eval``, ``multi``, ``argext`` or ``append``
    family: str
    #: what the step is bound to: `_ANY_DEVICE` (a jit serves whichever
    #: device its arguments sit on) or the `_OnMesh` it is mapped over
    place: tuple
    #: the op (``regular``), the ops over the one ring (``append_eval``),
    #: the (op, field) pairs (``multi``, ``argext``)
    stats: object
    cap: int                # ring columns
    Rb: int                 # the rectangle's columns, bucketed
    #: the window bucket: a launch's windows (a shard's, on a mesh); of the
    #: ``regular`` family the windows of a ring row (``C``)
    Bb: int
    KP: int                 # ring rows
    wires: object           # wire dtype of the rectangle, one a field
    accs: object            # ring dtype, one a field
    pad: int = 0            # padded window length of a (Bb, pad) gather
    fields: tuple = ()      # the ring fields, where each has its ring
    fn_slot: tuple = None   # the fields of a bound user's function
    slide: int = 0          # ``regular``: the windows' stride
    eb: int = 0             # ``argext``: cells a block of the walk reads


class _OneDevice(NamedTuple):
    """Placement of rings that live whole on one device: what a launch
    does there is what it costs every cell, so nothing here builds an
    array the step does not take."""
    device: object
    mesh = None
    n_shards = 1

    @property
    def in_key(self):
        return _ANY_DEVICE

    @property
    def rect(self):
        """Where a ring or a rectangle is put (`rows`: a per-row vector,
        `wins`: a window descriptor)."""
        return self.device

    rows = wins = rect

    def each(self, devices):
        """The placements to warm a step of this key's on."""
        return [_OneDevice(d) for d in devices]

    def ring_rows(self, n_keys: int, lo: int) -> int:
        return _bucket(n_keys, lo=lo)

    def phys_rows(self, rows, KP):
        """The ring row each dense key row lands on."""
        return np.asarray(rows)

    def win_shape(self, Bb: int):
        return (Bb,)

    def batch(self, wrows, B, lo):
        """(window bucket, harvest's selector, the windows' rows as the
        step takes them) of a launch of `B` windows."""
        return _bucket(max(B, 1), lo=lo), B, wrows

    def put(self, KP, Rb, Bb, sel, blks, rows, wins, heads=()):
        """ONE transfer of one tuple: the rectangle (a tuple of them a ring
        per field) padded to (KP, Rb) unless it comes so, per-row vectors to
        KP, window descriptors to Bb, int64 header columns to Bb."""
        def rect(b):
            return b if b.shape == (KP, Rb) else _pad2(b, KP, Rb)
        return jax.device_put(
            (tuple(rect(b) for b in blks) if isinstance(blks, tuple)
             else rect(blks),
             *[_pad1(a, KP) for a in rows], *[_pad1(a, Bb) for a in wins],
             *[_pad1(a if a is not None else (), Bb, dtype=np.int64)
               for a in heads]),
            self.device)

    def compile(self, step, name, n_rows, n_wins, **jit_kw):
        return _named_jit(step, name, **jit_kw)


#: the placement in the key of every one-device step
_ANY_DEVICE = _OneDevice(None)


class _OnMesh(NamedTuple):
    """Placement of rings sharded ``P(axis, None)`` over a
    ``jax.sharding.Mesh``: ring rows are distributed over the mesh's
    key-group axis, so ONE dispatch serves every key group — each chip holds
    its groups' archives in its own HBM and evaluates its own windows (no
    collectives; the kf axis is embarrassingly parallel, parallel/mesh.py).
    The multi-chip form of the reference's per-worker GPU ownership
    (win_farm_gpu.hpp:132-168) with the farm collapsed into one SPMD
    program."""
    mesh: object
    axis: str

    @property
    def device(self):
        return self.mesh.devices.flat[0]

    @property
    def n_shards(self) -> int:
        return int(self.mesh.shape[self.axis])

    @property
    def in_key(self):
        return self

    @property
    def rect(self):
        from jax.sharding import NamedSharding, PartitionSpec as P
        return NamedSharding(self.mesh, P(self.axis, None))

    wins = rect

    @property
    def rows(self):
        from jax.sharding import NamedSharding, PartitionSpec as P
        return NamedSharding(self.mesh, P(self.axis))

    def each(self, devices):
        return [self]

    def ring_rows(self, n_keys: int, lo: int) -> int:
        S = self.n_shards
        return S * _bucket(-(-n_keys // S), lo=lo)

    def phys_rows(self, rows, KP):
        # STRIDE dense key rows over shards (row r -> shard r % S, local
        # slot r // S): the host assigns rows in key-arrival order, so a
        # block mapping would concentrate all live keys on the low shards
        # while the padded tail idles — striding balances any K
        S = self.n_shards
        rows = np.asarray(rows, dtype=np.int64)
        return (rows % S) * (KP // S) + rows // S

    def win_shape(self, Bb: int):
        return (self.n_shards, Bb)

    def batch(self, wrows, B, lo):
        # a window goes to its row's shard, slots in arrival order per
        # shard; the bucket is the fullest shard's, and harvest indexes the
        # (S, Bs) result back to flat window order by (shard, slot)
        S = self.n_shards
        wrows = np.asarray(wrows, dtype=np.int64)
        shard = wrows % S
        slots = np.zeros(B, dtype=np.int64)
        maxc = 0
        for s in range(S):
            m = shard == s
            c = int(m.sum())
            slots[m] = np.arange(c)
            maxc = max(maxc, c)
        return _bucket(max(maxc, 1), lo=lo), (shard, slots), wrows // S

    def put(self, KP, Rb, Bb, sel, blks, rows, wins, heads=()):
        """One transfer an argument, each with its sharding: rectangles and
        per-row vectors scattered shard-major (`phys_rows`), a descriptor
        laid out (S, Bs) by `sel`'s (shard, slot)."""
        K, R = (blks[0] if isinstance(blks, tuple) else blks).shape
        prow = self.phys_rows(np.arange(K), KP)
        S, s2, s1 = self.n_shards, self.rect, self.rows

        def rect(b):
            bp = np.zeros((KP, Rb), dtype=b.dtype)
            bp[prow, :R] = b
            return jax.device_put(bp, s2)

        def row(a):
            out = np.zeros(KP, dtype=np.int32)
            out[prow] = a[:K]
            return jax.device_put(out, s1)

        def win(a, dtype=np.int32):
            out = np.zeros((S, Bb), dtype=dtype)
            # a caller that binds no function sends empty header columns
            if a is not None and len(a) == len(sel[0]):
                out[sel] = a
            return jax.device_put(out, s2)

        return (tuple(rect(b) for b in blks) if isinstance(blks, tuple)
                else rect(blks),
                *[row(a) for a in rows], *[win(a) for a in wins],
                *[win(a, np.int64) for a in heads])

    def compile(self, step, name, n_rows, n_wins, **jit_kw):
        """shard_map of a one-device `step` over the key-group axis: each
        device appends to its row block of the ring(s) and evaluates its own
        windows.  Per-shard views: rings and rectangles (rps, .), the
        `n_rows` per-row vectors (rps,), the `n_wins` descriptors (1, Bs)."""
        from jax.sharding import PartitionSpec as P
        p2, p1 = P(self.axis, None), P(self.axis)

        def local(rings, blks, *rest):
            rings, outs = step(rings, blks, *rest[:n_rows],
                               *[d[0] for d in rest[n_rows:]])
            if n_wins:
                outs = jax.tree.map(lambda o: o[None, :], outs)
            return rings, outs

        mapped = jax.shard_map(
            local, mesh=self.mesh,
            in_specs=(p2, p2) + (p1,) * n_rows + (p2,) * n_wins,
            out_specs=(p2, p2))
        return _named_jit(mapped, name + "_mesh", **jit_kw)


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
    if not isinstance(key, StepKey):
        # the parent's positional key, which benchmarks/tests/
        # record_wf_trace.py still passes (no PR but a `benchmark` one may
        # edit it)
        _t, op, cap, Rb, KP, C, wire, acc, slide = key
        key = StepKey("regular", _ANY_DEVICE, op, cap, Rb, C, KP, wire, acc,
                      slide=slide)
    cap, C, slide, acc_dt = key.cap, key.Bb, key.slide, np.dtype(key.accs)

    def step(ring, blk, offs, rcount, rstart0, rlen):
        return _regular_body(cap, C, slide, acc_dt, ring, blk, offs,
                             rstart0, rlen)

    return key.place.compile(step, "wf_step_regular", 4, 0)


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
    ops, cap, pad, acc_dt = key.stats, key.cap, key.pad, np.dtype(key.accs)

    def step(ring, blk, offs, wrows, wstarts, wlens):
        ring, outs = _append_eval(ops, cap, pad, acc_dt, ring, blk, offs,
                                  wrows, wstarts, wlens)
        return ring, (outs[0] if len(outs) == 1 else outs)

    return key.place.compile(step, "wf_step_append_eval", 1, 3)


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

    #: the smallest bucket of the ring's rows
    _row_floor = 8

    def __init__(self, op, place=None, depth: int = 8, acc_dtype=np.int32):
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
        self.acc_dtype = np.dtype(acc_dtype)
        self._ring = None
        self._init_queue(place, depth)

    def _init_queue(self, place, depth):
        #: where the rings live (`_OneDevice`, `_OnMesh`): everything a
        #: launch does differently on a mesh, it asks of this
        self.place = (_OneDevice(default_device()) if place is None
                      else place)
        self.device = self.place.device    # a mesh's first
        self.mesh = self.place.mesh        # None on one device
        self.depth = depth
        self.cap = 0          # ring columns (set on first reset)
        self.KP = 0           # ring rows (padded key count)
        # (meta, sel, device_out, t_dispatched_ns, (launch, shard, cause))
        self._inflight = deque()
        self._ready = []
        self._svc = deque(maxlen=32)   # recent dispatch→ready seconds
        self._svc_mean = 0.0
        self.dispatches = 0   # launches this executor sent to its device

    # ------------------------------------------------------------ lifecycle

    def reset(self, n_keys: int, cap: int):
        """(Re)allocate empty ring(s) of at least (n_keys, cap), lazily
        zeros on the next launch; contents are repopulated by that launch's
        rectangle (host rebase)."""
        self.KP = self.place.ring_rows(max(n_keys, 1), self._row_floor)
        self.cap = _bucket(max(cap, 16))
        self._rings_assign(None)

    def _ring_arr(self):
        if self._ring is None:
            self._ring = _zeros((self.KP, self.cap), self.acc_dtype,
                                self.place.rect)
        return self._ring

    # ---------------------------------------------------- checkpoint/restore

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
            jax.device_put(r, self.place.rect) for r in rings))

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
        return _wire_dtype(vals, self.acc_dtype, vals.dtype.kind == "f")

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
        place = self.place
        K, R = blk.shape
        if K > self.KP:
            raise ValueError("rectangle exceeds ring rows; reset() first")
        B = len(wstarts)
        Rb = _bucket(max(R, 1))
        Bb, sel, wrows = place.batch(wrows, B, 8)
        _check_ring_overflow(offs, Rb, self.cap)
        pad = (_bucket(int(wlens.max()) if B else 1)
               if any(o != "sum" for o in self.ops) else 0)
        key = StepKey("append_eval", place.in_key, self.ops, self.cap, Rb,
                      Bb, self.KP, blk.dtype.str, self.acc_dtype.str, pad)
        fn = _STEP_CACHE.get(key)
        if fn is None:
            fn = _STEP_CACHE[key] = _make_step(key)
        with profile.span("device_put", *tag):
            args = place.put(self.KP, Rb, Bb, sel, blk, (offs,),
                             (wrows, wstarts, wlens))
        profile.add("bytes_shipped", blk.nbytes)
        profile.add("rows_shipped", blk.size)
        profile.add("windows", B)
        with profile.span("dispatch", *tag) as sp:
            self._ring, out = fn(self._ring_arr(), *args)
            for o in (out if isinstance(out, tuple) else (out,)):
                o.copy_to_host_async()
        self._dispatched(meta, sel, out, sp, tag)

    def launch_regular(self, meta, blk: np.ndarray, offs: np.ndarray,
                       rcount: np.ndarray, rstart0: np.ndarray,
                       rlen: np.ndarray, slide: int, wrows: np.ndarray,
                       widx: np.ndarray, cmax: int = 0, tag=_NO_TAG):
        """Fused append+eval with *regular* window descriptors: per ring
        row, windows i in [0, rcount[r]) start at rstart0[r] + i*slide with
        length rlen[r] — only 3 per-key scalars cross the wire instead of
        3 arrays of B int32 (sum only; the host maps the (KP, C) result
        back to pending-window order via (wrows, widx)).  On a mesh the
        scalars shard with their rows and each device expands its own
        arithmetic window sequences."""
        if not (self.single and self.op == "sum"):
            raise ValueError("regular descriptors implemented for "
                             "single-stat sum")
        place = self.place
        K, R = blk.shape
        if K > self.KP:
            raise ValueError("rectangle exceeds ring rows; reset() first")
        Rb = _bucket(max(R, 1))
        C = _bucket(int(cmax) if cmax else
                    (int(rcount.max()) if len(rcount) else 1))
        _check_ring_overflow(offs, Rb, self.cap)
        key = StepKey("regular", place.in_key, self.op, self.cap, Rb, C,
                      self.KP, blk.dtype.str, self.acc_dtype.str,
                      slide=int(slide))
        fn = _STEP_CACHE.get(key)
        if fn is None:
            fn = _STEP_CACHE[key] = _make_regular_step(key)
        with profile.span("device_put", *tag):
            args = place.put(self.KP, Rb, 0, None, blk,
                             (offs, rcount, rstart0, rlen), ())
        profile.add("bytes_shipped", blk.nbytes)
        profile.add("rows_shipped", blk.size)
        profile.add("windows", len(wrows))
        with profile.span("dispatch", *tag) as sp:
            self._ring, out = fn(self._ring_arr(), *args)
            out.copy_to_host_async()
        self._dispatched(
            meta, (place.phys_rows(wrows, self.KP), np.asarray(widx)), out,
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
    mask = jnp.arange(pad, dtype=jnp.int32)[None, :] < wlens[:, None]

    def windows(ring):
        # a window is ONE stretch of its ring row: `pad` cells sliced from
        # its start, not a gather cell by cell (9 ns a cell on a v5e: 0.57 s
        # a column of a window of 3 x 10^7 rows, PERF.md PR 50).  The row is
        # widened by `pad` zeros first, so a stretch that reaches past the
        # ring's end reads them there -- cells the mask drops -- and is not
        # moved back
        wide = jnp.pad(ring, ((0, 0), (0, pad)))
        return jax.vmap(lambda r, s: lax.dynamic_slice(
            wide, (r, s), (1, pad))[0])(wrows, wstarts)

    cols = {f: jnp.where(mask, windows(rings[fidx[f]]), 0) for f in fields}
    with jax.named_scope("wf_udf"):
        res = fn_ref()(wkeys, wgwids, cols, mask)
    return res if isinstance(res, tuple) else (res,)


def _make_multi_step(key, jax_fn):
    """Fused multi-field append + eval: one ring per field, reducer stats
    evaluate over their field's ring, and an optional batched JAX window
    function (JaxWindowFunction) reads (B, pad) gathers of every field —
    the device-resident form of the reference's arbitrary device functor
    over whole POD tuples (win_seq_gpu.hpp:54-67): every column crosses
    the wire once, the functor reads HBM.  On a mesh each device appends its
    row block of EVERY field's ring and evaluates its own windows' stats and
    function (windows are row-local: the multi-chip form of the whole-tuple
    functor contract, SURVEY §2.8)."""
    stats, cap, pad = key.stats, key.cap, key.pad
    acc_dts = tuple(np.dtype(a) for a in key.accs)
    fidx = {f: i for i, f in enumerate(key.fields)}
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

    return key.place.compile(step, "wf_step_multi", 1, 5)


def _make_append_step(key):
    """Append alone, for a launch that evaluates nothing: each field's
    rectangle into its ring, the rings donated so that it is in place.  A
    core that ships a window's rows long before the window closes (the
    join's, patterns/win_join_tpu.py) sends them so and keeps the step bound
    to its function for the launch that closes the window."""
    acc_dts = tuple(np.dtype(a) for a in key.accs)

    def step(rings, blks, offs):
        return tuple(_ring_append(r, b, offs, dt)
                     for r, b, dt in zip(rings, blks, acc_dts)), ()

    return key.place.compile(step, "wf_step_append", 1, 0, donate_argnums=0)


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
                 place=None, depth: int = 8, row_floor: int = None):
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
        self._rings = None
        self._init_queue(place, depth)
        if row_floor is not None:
            # a core that knows its keys (the join's one) sizes the rings'
            # rows itself: eight rows of a window's length are eight windows
            self._row_floor = int(row_floor)
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

    def _rings_arr(self):
        if self._rings is None:
            self._rings = tuple(
                _zeros((self.KP, self.cap), self.acc_dtypes[f],
                       self.place.rect) for f in self.fields)
        return self._rings

    def _rings_tuple(self):
        return self._rings

    def _rings_assign(self, rings):
        self._rings = rings

    def narrow_for(self, field, vals: np.ndarray) -> np.dtype:
        """Per-field wire narrowing (same ladder as the base class but
        bounded by that field's ring dtype)."""
        acc = self.acc_dtypes[field]
        if len(vals) and vals.dtype.kind == "f" and acc.kind != "f":
            raise ValueError(
                f"float column {field!r} headed into a {acc} ring would "
                "silently truncate — declare a float ring dtype "
                f"(JaxWindowFunction(field_dtypes={{{field!r}: "
                "np.float32}}))")
        return _wire_dtype(vals, acc, acc.kind == "f")

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
            # a function that declares its window's rows keeps ONE shape
            # while the windows stay under it
            return _bucket_fine(max(longest, self.jax_fn.window_rows or 0))
        if any(op != "sum" for op, _f in self.stats):
            return _bucket(longest)
        return 0

    def grow(self, cap: int):
        """Widen every ring to `cap` cells a row on the device, contents
        kept (a core asks when its live rows outgrow the ring)."""
        if cap <= self.cap:
            return
        old = self._rings_arr()
        self.cap = cap
        self._rings = None
        # fresh rings as :meth:`_rings_arr` makes them, the old contents
        # written at the front
        self._rings = tuple(lax.dynamic_update_slice(z, r, (0, 0))
                            for z, r in zip(self._rings_arr(), old))

    def append(self, blks: dict, offs: np.ndarray, tag=_NO_TAG):
        """A dispatch that only appends the per-field rectangles `blks[f]`
        (K, R) at `offs`: nothing is evaluated, nothing comes back, nothing
        waits for a harvest (``jit_wf_step_append``)."""
        place = self.place
        K, R = next(iter(blks.values())).shape
        if K > self.KP:
            raise ValueError("rectangle exceeds ring rows; reset() first")
        Rb = _bucket(max(R, 1))
        _check_ring_overflow(offs, Rb, self.cap)
        key = StepKey(
            "append", place.in_key, (), self.cap, Rb, 0, self.KP,
            tuple(blks[f].dtype.str for f in self.fields),
            tuple(self.acc_dtypes[f].str for f in self.fields),
            fields=self.fields)
        fn = _STEP_CACHE.get(key)
        if fn is None:
            fn = _STEP_CACHE[key] = _make_append_step(key)
        with profile.span("device_put", *tag):
            args = place.put(self.KP, Rb, 0, None,
                             tuple(blks[f] for f in self.fields), (offs,), ())
        for f in self.fields:
            profile.add("bytes_shipped", blks[f].nbytes)
            profile.add("rows_shipped", blks[f].size)
        with profile.span("dispatch", *tag):
            self._rings, _none = fn(self._rings_arr(), *args)
        self.dispatches += 1
        stats_add("dispatches")

    def launch(self, meta, blks: dict, offs: np.ndarray,
               wrows: np.ndarray, wstarts: np.ndarray, wlens: np.ndarray,
               wkeys: np.ndarray = None, wgwids: np.ndarray = None,
               tag=_NO_TAG):
        """One fused dispatch: per-field rectangles `blks[f]` (K, R) append
        at `offs`, then every stat / the JAX fn evaluates the described
        windows.  `wkeys`/`wgwids` are required when a JAX fn is bound."""
        place = self.place
        K, R = next(iter(blks.values())).shape
        if K > self.KP:
            raise ValueError("rectangle exceeds ring rows; reset() first")
        B = len(wstarts)
        Rb = _bucket(max(R, 1))
        Bb, sel, wrows = place.batch(wrows, B, self._batch_floor)
        _check_ring_overflow(offs, Rb, self.cap)
        pad = self._pad_for(wlens)
        key = StepKey(
            "multi", place.in_key, self.stats, self.cap, Rb, Bb, self.KP,
            tuple(blks[f].dtype.str for f in self.fields),
            tuple(self.acc_dtypes[f].str for f in self.fields), pad,
            self.fields, self._fn_slot)
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
            args = place.put(self.KP, Rb, Bb, sel,
                             tuple(blks[f] for f in self.fields), (offs,),
                             (wrows, wstarts, wlens), (wkeys, wgwids))
        for f in self.fields:
            profile.add("bytes_shipped", blks[f].nbytes)
            profile.add("rows_shipped", blks[f].size)
        profile.add("windows", B)
        if self.jax_fn is not None:
            self._count_udf(B, wlens, place.n_shards * Bb * pad)
        with profile.span("dispatch", *tag) as sp:
            self._rings, out = fn(self._rings_arr(), *args)
            for o in out:
                o.copy_to_host_async()
        self._dispatched(meta, sel, out, sp, tag)

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
    stats, cap, pad, eb = key.stats, key.cap, key.pad, key.eb
    acc_dts = tuple(np.dtype(a) for a in key.accs)
    fidx = {f: i for i, f in enumerate(key.fields)}

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

    return key.place.compile(step, "wf_step_argext", 2, 3, donate_argnums=0)


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
    _row_floor = 1

    def __init__(self, fields, stats, acc_dtypes, place=None,
                 depth: int = 8):
        super().__init__(fields, stats=stats, acc_dtypes=acc_dtypes,
                         place=place, depth=depth)
        if self.mesh is not None:
            raise ValueError("the arg-extremum family serves no mesh")
        self.eval_block = ARGEXT_BLOCK

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
        key = StepKey(
            "argext", _ANY_DEVICE, self.stats, self.cap, Rb, Bb, self.KP,
            tuple(blks[f].dtype.str for f in self.fields),
            tuple(self.acc_dtypes[f].str for f in self.fields), pad,
            self.fields, eb=min(self.eval_block, self.cap))
        fn = _STEP_CACHE.get(key)
        if fn is None:
            fn = _STEP_CACHE[key] = _make_argext_step(key)
        with profile.span("device_put", *tag):
            args = self.place.put(
                self.KP, Rb, Bb, B, tuple(blks[f] for f in self.fields),
                (offs, shifts if shifts is not None else ()),
                (wrows, wstarts, wlens))
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


def make_executor(family: str, fields, stats, acc_dtypes, *, jax_fn=None,
                  mesh=None, device=None, depth: int = 8,
                  row_floor: int = None):
    """The resident executor of one step family — the one place that names
    the executor classes.  ``family`` is ``"regular"`` (one ring, every op
    of ``stats`` over it), ``"multi"`` (a ring per field; the only family
    that takes a ``jax_fn``) or ``"argext"``; ``stats`` are (op, field)
    pairs and ``acc_dtypes`` maps each field to its ring dtype.  With
    ``mesh`` the rings shard ``P(kf, None)`` over it, else they live on
    ``device``.  ``row_floor`` (``multi`` alone) is the smallest bucket of
    the rings' rows where it is not the family's own."""
    if mesh is None:
        place = _OneDevice(device or default_device())
    elif "kf" not in mesh.shape:
        raise ValueError(f"mesh has no axis 'kf': {mesh.shape}")
    else:
        place = _OnMesh(mesh, "kf")
    if family == "argext":
        return ArgExtResidentExecutor(fields, stats, acc_dtypes, place=place,
                                      depth=depth)
    if family == "multi":
        return MultiFieldResidentExecutor(
            fields, stats=stats, jax_fn=jax_fn, acc_dtypes=acc_dtypes,
            place=place, depth=depth, row_floor=row_floor)
    (field,) = fields
    ops = tuple(op for op, _f in stats)
    return ResidentWindowExecutor(ops[0] if len(ops) == 1 else ops,
                                  place=place, depth=depth,
                                  acc_dtype=acc_dtypes[field])


def prewarm_regular_ladder(mults=(2, 4, 8, 16), devices=None,
                           max_cells=1 << 24) -> int:
    """Compile the coalesced-shape siblings of every one-ring step
    (``regular``, ``append_eval``; one device or a mesh) already compiled in
    this process.

    Deep launch coalescing dispatches merged shapes on the {2x, 4x, ...}
    buddy ladder only when launches queue up behind a slow service, exactly
    when a cold mid-run compile hurts most.  A benchmark calls this once
    after its warmup run: whatever buckets the warmup compiled, their
    ladder siblings compile now, deterministically, regardless of the
    launch service the warmup happened to see.  ``devices`` should list
    every device the run's executors own (jit executables cache per
    placement; a farm worker on another chip would otherwise cold-compile
    its first merged shape) — default is device 0 only.  Returns the
    number of steps compiled."""
    devices = list(devices) if devices else [default_device()]
    factory = {"regular": _make_regular_step, "append_eval": _make_step}
    warmed = 0
    for key in list(_STEP_CACHE):
        # a prewarmed sibling never seeds further ladders: the buddy
        # multiplicity caps at 16x of a NATURAL launch shape, so
        # ladders-of-ladders are undispatchable (and repeat calls must be
        # no-ops).  The families with a ring per field are not warmed: the
        # coalescer merges one-ring launches only
        if key in _PREWARMED or key.family not in factory:
            continue
        for m in mults:
            # a real merge can never exceed the ring (try_merge's offset
            # guard bounds bucket(newR) by cap) ...
            if key.Rb * m > key.cap:
                continue
            # ... and its area guard counts LIVE keys (K2 * bucket(newR)
            # <= max_cells, wf_native.cpp:try_merge); the smallest live K
            # a KP-row launch can carry is KP//2 + 1 (bucket property), so
            # skip only shapes NO admissible merge could produce — a
            # padded-KP guard here would refuse shapes the coalescer then
            # builds and compiles cold mid-run
            if (key.KP // 2 + 1) * key.Rb * m > max_cells:
                continue
            if key.family == "append_eval" and key.place.mesh is None:
                # explicit descriptors on one device (TB windows, non-sum
                # ops): a merge doubles the rectangle AND the window bucket,
                # the diagonal (Rb*m, Bb*m)
                grow = [m]
            else:
                # the LOWER TRIANGLE {(Rb*m, Bb*b), b <= m}.  Regular
                # merges: small per-key window counts can clamp the C
                # bucket while rows double (try_merge admits rc <= rr), so
                # the diagonal alone would leave e.g. (2*Rb, C) cold exactly
                # when the coalescer builds it mid-stall (ADVICE r3).  A
                # mesh's explicit descriptors: its window bucket is PER
                # SHARD (the fullest shard's, `_OnMesh.batch`) while
                # try_merge guards the TOTAL, so merged per-shard counts can
                # sit under the lo=8 clamp while rows double
                grow = [1 << i for i in range(m.bit_length())]
            todo = [sk for sk in (key._replace(Rb=key.Rb * m, Bb=key.Bb * b)
                                  for b in grow) if sk not in _STEP_CACHE]
            if not todo:
                continue
            # the warm inputs depend only on (family, m), never on the
            # sibling's window bucket (of the regular family it shapes the
            # OUTPUT only) — allocate them once per placement and reuse
            # across siblings (a ring is up to 128 MB; re-shipping it per
            # sibling would stretch the warmup window for nothing)
            bases = [
                (place,
                 _zeros((key.KP, key.cap), np.dtype(key.accs), place.rect),
                 _zeros((key.KP, key.Rb * m), np.dtype(key.wires),
                        place.rect),
                 _zeros(key.KP, np.int32, place.rows))
                for place in key.place.each(devices)]
            for sk in todo:
                # cache only AFTER the warm dispatch succeeds: a transient
                # device error mid-warm must leave the key retryable, not
                # "warm" with a cold executable behind it
                fn = factory[key.family](sk)
                for place, ring, blk, zk in bases:
                    # regular steps take per-key scalars only; the others'
                    # descriptors are the one input shaped by the sibling
                    zb = zk if key.family == "regular" else _zeros(
                        place.win_shape(sk.Bb), np.int32, place.wins)
                    _ring2, out = fn(ring, blk, zk, zb, zb, zb)
                    jax.block_until_ready(out)
                _STEP_CACHE[sk] = fn
                _PREWARMED.add(sk)
                warmed += 1
    return warmed
