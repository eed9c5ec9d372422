"""TPU device execution of window batches — the graft replacing the CUDA
micro-batch path (reference win_seq_gpu.hpp).

The reference fires windows into batch vectors and, at ``batch_len``, copies
``(Bin, start, end, gwids)`` to the GPU and launches one kernel with one
window per CUDA thread (win_seq_gpu.hpp:429-501), synchronising per batch
(:481).  The TPU design differs where it should:

* **Staging**: the window batch is described as a *flat* buffer of archive
  rows plus per-window (start, len) — the flat buffer is staged once even
  though consecutive sliding windows overlap (the device-side analog of the
  reference's refcounted host-side multicast, meta_utils.hpp:354).
* **Compute**: one XLA computation evaluates all windows: a gather expands
  ``flat[start_i + j]`` into a (B, pad) tile, a mask kills the padding, and
  the reduction runs on the VPU.
* **Shapes**: XLA needs static shapes where CUDA took runtime sizes, so
  (B, pad, N) are bucketed to powers of two and jits are cached per bucket —
  the recompile-amortisation answer to win_seq_gpu.hpp:462-473's grow/shrink
  heuristic.
* **Overlap**: launches are asynchronous (JAX dispatch); up to ``depth``
  batches are in flight before the host blocks, replacing the reference's
  blocking ``cudaStreamSynchronize`` per batch — strictly more overlap.

User-function contract: a JAX function ``fn(keys, gwids, cols, mask) ->
result column(s)`` over the whole window batch (cols[field]: (B, pad)).
Built-in reductions provide it out of the box; arbitrary *host* Python
functions cannot be staged to the device (XLA cannot JIT host code — the
same restriction the reference's CUDA path has, where the functor must be a
__device__ lambda) and use the host path instead.
"""

from __future__ import annotations

import functools

import numpy as np

import jax
import jax.numpy as jnp

from .backend import default_device
from .monoid import identity as _monoid_identity
from .monoid import jnp_reducer

_INT32_MIN, _INT32_MAX = -(2 ** 31), 2 ** 31 - 1

#: process-wide compiled-function cache — executors come and go per pattern
#: instance, the executables they compile should not
_JIT_CACHE = {}


def _bucket(n: int, lo: int = 8) -> int:
    """Next power of two >= n (shape bucketing for jit reuse)."""
    b = lo
    while b < n:
        b *= 2
    return b


#: steps a doubling of `_bucket_fine`'s ladder
_FINE_STEPS = 8


def _bucket_fine(n: int) -> int:
    """Next step >= n of a geometric ladder of `_FINE_STEPS` a doubling,
    ``2^k * (8 + j) / 8``, from 1,024 up; the power of two under it.  The
    padded length of a window handed to a user's function, whose cost goes
    with every cell and, all pairs, with their square: at most an eighth
    over `n` where `_bucket` is up to twice it, never over `_bucket(n)`, and
    every step a multiple of 128 lanes."""
    b = _bucket(n)
    if b <= 1024:
        return b
    step = b // (2 * _FINE_STEPS)
    return -(-n // step) * step


def cast_result(vals: np.ndarray, dt) -> np.ndarray:
    """A device output's rows in their result field's dtype.  A sub-array
    field, ``(base, shape)`` -- a container-valued result, as a pane's
    frontier in ``shape`` slots -- takes an output of ``(B, *shape)`` in its
    base dtype: ``astype`` with the sub-array dtype itself would broadcast
    every element to ``shape``."""
    dt = np.dtype(dt)
    if vals.shape[1:] != dt.shape:
        raise ValueError(
            f"a window function's output of shape {vals.shape} does not fit "
            f"its result field of dtype {dt}: want (B,"
            f"{''.join(f' {n},' for n in dt.shape)})")
    return vals.astype(dt.base, copy=False)


@functools.lru_cache(maxsize=None)
def builtin_batch_fn(op: str, field: str = "value"):
    """Batched window function for a built-in reduction, in JAX.  Cached so
    every executor evaluating the same (op, field) shares one function
    object — and therefore one compiled-executable cache entry."""

    def fn(keys, gwids, cols, mask):
        if op == "count":
            return jnp.sum(mask, axis=1)
        vals = cols[field]
        if op == "mean":
            s = jnp.sum(jnp.where(mask, vals, 0), axis=1)
            c = jnp.maximum(jnp.sum(mask, axis=1), 1)
            return s / c
        ident = _monoid_identity(op, vals.dtype)
        return jnp_reducer(op)(jnp.where(mask, vals, ident), axis=1)

    fn._windflow_shared = True  # safe to cache executables process-wide
    return fn


class DeviceWindowExecutor:
    """Compiles and launches batched window evaluations with bucketed
    shapes and bounded asynchronous depth."""

    def __init__(self, batch_fn, fields=("value",), out_fields=("value",),
                 device=None, depth: int = 4, compute_dtype=None,
                 out_dtypes=None, empty_fill=None):
        self.batch_fn = batch_fn
        self.fields = tuple(fields)
        self.out_fields = tuple(out_fields)
        self.device = device or default_device()
        self.depth = depth
        self.compute_dtype = compute_dtype
        # result dtypes per out_field: harvest casts into them so that
        # empty-window fills (below) can hold full-width identities
        self.out_dtypes = {f: np.dtype(d) for f, d in (out_dtypes or {}).items()}
        # {field: value} written over empty windows at harvest — keeps the
        # device path's empty-window results identical to the host path's
        # even when compute happens in a narrower dtype (int32 vs int64)
        self.empty_fill = dict(empty_fill or {})
        # Executables compiled for process-lifetime functions (the lru-cached
        # builtins, or anything marked _windflow_shared) go in the process-
        # wide cache so new executor instances reuse them; ad-hoc user
        # functions keep a per-instance cache (a global entry keyed on a
        # short-lived lambda could never be reused but never dies either).
        shared = getattr(batch_fn, "_windflow_shared", False)
        self._jits = _JIT_CACHE if shared else {}
        self.launches = 0    # batches this executor sent to its device
        self._inflight = []  # [(meta, B, empty_mask, device_results)]
        self._ready = []     # harvested result batches (host)
        self._warned_downcast = False
        self._warned_id_range = False

    # ----------------------------------------------------------- compilation

    def _compiled(self, B, pad, N):
        # the jitted callable closes over (pad, N) only; B varies through the
        # argument shapes, which jax.jit re-specialises on by itself.  Keyed
        # process-wide on the user function object so a new executor (a new
        # pattern instance, a re-run pipeline) reuses executables already
        # compiled for the same function and bucket.
        key = (self.batch_fn, pad, N)
        fn = self._jits.get(key)
        if fn is not None:
            return fn
        batch_fn = self.batch_fn

        def run(flat_cols, starts, lens, keys, gwids):
            idx = starts[:, None] + jnp.arange(pad, dtype=jnp.int32)[None, :]
            idx = jnp.minimum(idx, N - 1)
            mask = jnp.arange(pad, dtype=jnp.int32)[None, :] < lens[:, None]
            cols = {f: jnp.where(mask, flat_cols[f][idx], 0)
                    for f in flat_cols}
            out = batch_fn(keys, gwids, cols, mask)
            return out if isinstance(out, tuple) else (out,)

        fn = jax.jit(run)
        self._jits[key] = fn
        return fn

    # ------------------------------------------------------------- execution

    def launch(self, meta, flat_cols: dict, starts: np.ndarray,
               lens: np.ndarray, keys: np.ndarray, gwids: np.ndarray):
        """Asynchronously evaluate one window batch.  `meta` is returned
        with the results at harvest time (host-side result headers)."""
        B = len(starts)
        Bb = _bucket(B)
        pad = _bucket(int(lens.max()) if len(lens) else 1)
        n = len(next(iter(flat_cols.values()))) if flat_cols else 1
        Nb = _bucket(max(n, 1) + pad)

        def pad1(a, size, dtype=None):
            a = np.asarray(a)
            out = np.zeros(size, dtype=dtype or a.dtype)
            out[:len(a)] = a
            return out

        dcols = {}
        for f, col in flat_cols.items():
            col = np.asarray(col)
            if self.compute_dtype is not None and col.dtype.kind in "iuf":
                col = col.astype(self.compute_dtype)
            elif col.dtype == np.int64:
                # TPU-native integer width; reductions exceeding int32 range
                # will wrap — pick compute_dtype explicitly for wide sums
                if not self._warned_downcast:
                    self._warned_downcast = True
                    import warnings
                    warnings.warn(
                        "device path downcasts int64 payloads to int32; "
                        "window reductions beyond ±2^31 will overflow — pass "
                        "compute_dtype (e.g. np.float32) for wide ranges",
                        stacklevel=3)
                col = col.astype(np.int32)
            dcols[f] = pad1(col, Nb)
        if not self._warned_id_range:
            for name, a in (("keys", keys), ("gwids", gwids)):
                fits = (a.dtype.kind == "i" and a.dtype.itemsize <= 4) or \
                       (a.dtype.kind == "u" and a.dtype.itemsize <= 2)
                if fits or not len(a):
                    continue  # provably within int32: skip the O(B) scan
                mx, mn = int(a.max()), int(a.min())
                if mx > _INT32_MAX or mn < _INT32_MIN:
                    self._warned_id_range = True
                    bad = mx if mx > _INT32_MAX else mn
                    import warnings
                    warnings.warn(
                        f"device path downcasts {name} to int32 and "
                        f"{bad} is out of range; a window function "
                        "reading them will see wrapped values", stacklevel=3)
        args = jax.device_put(
            (dcols,
             pad1(starts.astype(np.int32), Bb),
             pad1(lens.astype(np.int32), Bb),
             pad1(keys.astype(np.int32), Bb),
             pad1(gwids.astype(np.int32), Bb)),
            self.device)
        out = self._compiled(Bb, pad, Nb)(*args)
        self.launches += 1
        for o in out:
            # start the D2H transfer now so harvest finds it on host
            o.copy_to_host_async()
        empty = lens == 0 if self.empty_fill and (lens == 0).any() else None
        self._inflight.append((meta, B, empty, out))
        while len(self._inflight) > self.depth:
            self._harvest_one()

    def _harvest_one(self):
        meta, B, empty, out = self._inflight.pop(0)
        host = [np.asarray(o)[:B] for o in out]  # blocks until ready
        cols = {}
        for f, v in zip(self.out_fields, host):
            dt = self.out_dtypes.get(f)
            if dt is not None:
                v = cast_result(v, dt)
            if empty is not None and f in self.empty_fill:
                v = v.copy() if v.base is not None else v
                v[empty] = self.empty_fill[f]
            cols[f] = v
        self._ready.append((meta, cols))

    def poll(self):
        """Harvest any completed launches without blocking on new ones;
        returns [(meta, {field: values})]."""
        while self._inflight and self._is_ready(self._inflight[0][3]):
            self._harvest_one()
        ready, self._ready = self._ready, []
        return ready

    @staticmethod
    def _is_ready(out) -> bool:
        return all(o.is_ready() for o in out)

    def drain(self):
        """Block until every in-flight batch is harvested."""
        while self._inflight:
            self._harvest_one()
        ready, self._ready = self._ready, []
        return ready
