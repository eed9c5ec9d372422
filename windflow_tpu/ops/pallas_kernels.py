"""Pallas TPU kernels for windowed reductions.

The XLA gather path (ops/device.py) materialises a (B, pad) tile in HBM
before reducing; for large windows that tile dominates memory traffic.
This kernel instead walks the *flat* staged buffer directly: the whole
buffer sits in VMEM as ``(N/128, 128)`` and each window reads only the
aligned rows that cover it, so HBM traffic is O(flat + B) instead of
O(B * pad) — the sliding-window overlap between consecutive windows is read
from VMEM, not re-fetched from HBM.

Mosaic refuses a lane-unaligned dynamic slice (``flat[s : s+pad]`` with a
runtime ``s``), so no such slice exists here: window ``w`` loads rows
``[s // 128, s // 128 + rows)`` with a dynamic *sublane* start and masks by
position inside that tile.  One program reduces up to 1024 windows (the
analog of the reference's one-window-per-CUDA-thread kernel,
win_seq_gpu.hpp:54-67) and writes their results lane-dense.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .monoid import identity as _identity
from .monoid import jnp_reducer

_LANES = 128
#: windows per program; XLA tiles a long rank-1 int32 operand by 1024, and
#: an SMEM block has to match that tiling
_MAX_WPP = 1024
#: the flat buffer is held whole in VMEM (128 MiB on a v5e core, of which
#: the kernel asks for the buffer plus headroom); larger batches are refused
MAX_FLAT_BYTES = 32 << 20
_VMEM_HEADROOM = 16 << 20


def _cover_rows(pad: int) -> int:
    """Aligned 128-lane rows that cover any window of up to `pad` elements
    whatever its start's offset inside the first row."""
    return -(-(pad + _LANES - 1) // _LANES)


def flat_slack(pad: int) -> int:
    """Elements the caller must keep allocated past the last window start:
    the covering rows of a window reach at most this far beyond it."""
    return _cover_rows(pad) * _LANES


def _kernel(starts_ref, lens_ref, flat_ref, out_ref, *, rows, wpp, op,
            dtype):
    ident = _identity(op, dtype)
    pos = (lax.broadcasted_iota(jnp.int32, (rows, _LANES), 0) * _LANES
           + lax.broadcasted_iota(jnp.int32, (rows, _LANES), 1))
    lane = lax.broadcasted_iota(jnp.int32, (1, _LANES), 1)

    def one_window(base, g, acc):
        s = starts_ref[base + g]
        l = lens_ref[base + g]
        if op == "count":
            v = l.astype(dtype)
        else:
            r0 = s // _LANES
            off = s - r0 * _LANES
            tile = flat_ref[pl.ds(r0, rows), :]
            live = (pos >= off) & (pos < off + l)
            v = jnp_reducer(op)(jnp.where(live, tile, ident))
        return jnp.where(lane == g, v, acc)

    def one_row(j, carry):
        acc = lax.fori_loop(
            0, _LANES, functools.partial(one_window, j * _LANES),
            jnp.full((1, _LANES), ident, dtype))
        out_ref[0, pl.ds(j, 1), :] = acc
        return carry

    lax.fori_loop(0, wpp // _LANES, one_row, 0)


@functools.partial(jax.jit, static_argnames=("pad", "op", "interpret"))
def windowed_reduce_pallas(flat, starts, lens, pad, op, interpret=False):
    """Reduce B windows (flat[starts[i] : starts[i]+lens[i]], lens <= pad)
    with the monoid `op`.  ``flat`` is a 32-bit column whose length is a
    multiple of 128 and reaches :func:`flat_slack` elements past every
    window start."""
    B = starts.shape[0]
    N = flat.shape[0]
    if flat.dtype.itemsize != 4:
        raise ValueError(f"pallas window kernel needs a 32-bit column, "
                         f"got {flat.dtype}")
    if N % _LANES:
        raise ValueError(f"flat length {N} is not a multiple of {_LANES}")
    if N * 4 > MAX_FLAT_BYTES:
        raise ValueError(
            f"flat buffer of {N * 4} bytes exceeds the {MAX_FLAT_BYTES} the "
            "pallas window kernel holds in VMEM; lower batch_len or use the "
            "XLA gather path")
    rows = _cover_rows(pad)
    unit = _LANES if B <= _MAX_WPP else _MAX_WPP
    Bp = -(-B // unit) * unit
    wpp = min(Bp, _MAX_WPP)
    if Bp != B:   # padded windows have length 0 and reduce to the identity
        starts = jnp.pad(starts, (0, Bp - B))
        lens = jnp.pad(lens, (0, Bp - B))
    kernel = functools.partial(_kernel, rows=rows, wpp=wpp, op=op,
                               dtype=flat.dtype)
    out = pl.pallas_call(
        kernel,
        grid=(Bp // wpp,),
        in_specs=[
            pl.BlockSpec((wpp,), lambda i: (i,), memory_space=pltpu.SMEM),
            pl.BlockSpec((wpp,), lambda i: (i,), memory_space=pltpu.SMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),   # whole flat buffer
        ],
        out_specs=pl.BlockSpec((1, wpp // _LANES, _LANES),
                               lambda i: (i, 0, 0)),
        out_shape=jax.ShapeDtypeStruct(
            (Bp // wpp, wpp // _LANES, _LANES), flat.dtype),
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=N * 4 + _VMEM_HEADROOM),
        interpret=interpret,
    )(starts, lens, flat.reshape(N // _LANES, _LANES))
    return out.reshape(Bp)[:B]
