"""Builds the ``spatial_pf`` deployment through ``MultiPipe`` and the public
patterns, from the sizes in ``spatial_pf.json``.  The only file of this
configuration that imports the program.

The two functions are the deployment's own, kept here as the reference keeps
``skytree.hpp`` beside ``test_spatial_pf.cpp``.  The pane stage is a device
function against the library's contract for a user's function whose result
is a container (``JaxWindowFunction``: ``fn(keys, gwids, cols, mask)`` over
``(B, pad)`` gathers of a pane's rows; ``count_field`` names the count of the
``cap`` slots): the all-pairs dominance test, then the frontier's points
compacted into ``cap`` slots.  The window stage is a plain numpy
``WindowFunction`` on the host: the skyline of the frontiers of a window's
panes."""

from __future__ import annotations

import functools

import jax.numpy as jnp
import numpy as np

from windflow_tpu.api import MultiPipe
from windflow_tpu.core.tuples import Schema
from windflow_tpu.core.windows import WinType
from windflow_tpu.ops.functions import WindowFunction
from windflow_tpu.patterns.basic import Sink, Source
from windflow_tpu.patterns.win_seq_tpu import JaxWindowFunction, PaneFarmTPU

SCHEMA = Schema(x=np.float32, y=np.float32)
RESULT_FIELDS = {"size": np.int64, "checksum": np.float64}


def pane_fields(cap):
    """A pane's frontier as the engine carries it: ``cap`` slots a
    coordinate, in the rings' precision, and the count of those that hold a
    point."""
    return {"sk_x": np.dtype((np.float32, (cap,))),
            "sk_y": np.dtype((np.float32, (cap,))),
            "sk_n": np.int64}


@functools.lru_cache(maxsize=None)
def pane_skyline(cap):
    """The device function of the pane stage (ONE function object a ``cap``:
    the library keeps a function's compiled steps under it, so a second
    pipeline finds the first one's): each pane's skyline
    (minimisation in both coordinates: point ``j`` dominates point ``i`` iff
    ``x_j <= x_i``, ``y_j <= y_i`` and one of them strictly, so identical
    points leave each other alive) by the all-pairs test, its points then
    compacted in arrival order into ``cap`` slots.  ``sk_n`` is the
    frontier's true size, beyond ``cap`` too: the library raises on the host
    where it is."""
    slots = jnp.arange(cap, dtype=jnp.int32)

    def fn(keys, gwids, cols, mask):
        x, y = cols["x"], cols["y"]                   # (B, pad)
        xi, yi = x[:, :, None], y[:, :, None]         # the point judged
        xj, yj = x[:, None, :], y[:, None, :]         # ... against every j
        dominated = ((xj <= xi) & (yj <= yi) & ((xj < xi) | (yj < yi))
                     & mask[:, None, :])
        alive = mask & ~jnp.any(dominated, axis=2)
        # the frontier's k-th point goes to slot k: one term a slot, so the
        # sum is the coordinate itself
        rank = jnp.cumsum(alive, axis=1, dtype=jnp.int32) - 1
        put = alive[:, None, :] & (rank[:, None, :] == slots[None, :, None])
        sk_x = jnp.sum(jnp.where(put, x[:, None, :], 0), axis=2)
        sk_y = jnp.sum(jnp.where(put, y[:, None, :], 0), axis=2)
        return sk_x, sk_y, jnp.sum(alive, axis=1, dtype=jnp.int32)

    return fn


def pane_function(cap, ring_dtype=np.float32):
    """The pane skyline as the library takes a user's device function: its
    two coordinate rings in ``ring_dtype`` (the configuration's
    ``precision``: float32)."""
    return JaxWindowFunction(pane_skyline(cap), fields=("x", "y"),
                             result_fields=pane_fields(cap),
                             field_dtypes={"x": ring_dtype, "y": ring_dtype},
                             count_field="sk_n")


class WindowMerge(WindowFunction):
    """The host function of the window stage: the skyline of the points on
    the frontiers of a window's panes, every pair of them tested (some
    twenty frontiers of a dozen points), as ``size`` and ``checksum`` (the
    sum of ``x + y`` over it, in float64: whole numbers, exact)."""

    result_fields = dict(RESULT_FIELDS)
    required_fields = ("sk_x", "sk_y", "sk_n")

    def apply(self, key, gwid, rows):
        held = (np.arange(rows["sk_x"].shape[1])[None, :]
                < rows["sk_n"][:, None])
        x = rows["sk_x"][held].astype(np.float64)
        y = rows["sk_y"][held].astype(np.float64)
        dominated = ((x[None, :] <= x[:, None]) & (y[None, :] <= y[:, None])
                     & ((x[None, :] < x[:, None]) | (y[None, :] < y[:, None])))
        alive = ~dominated.any(axis=1)
        return int(np.count_nonzero(alive)), float((x + y)[alive].sum())


def record_dtype(cfg):
    return SCHEMA.dtype()


def window_workers(cfg):
    """The device window workers: the pane stage's."""
    return int(cfg["shapes"]["plq_degree"])


def build(cfg, source_fn, sink_fn, trace_dir=None, name="spatial_pf"):
    shp = cfg["shapes"]
    return (MultiPipe(name, trace_dir=trace_dir)
            .add_source(Source(source_fn, SCHEMA, name="sq_gen", fresh=True))
            .add(PaneFarmTPU(pane_function(int(shp["cap"])), WindowMerge(),
                             int(shp["win_us"]), int(shp["slide_us"]),
                             WinType[shp["win_type"]],
                             plq_degree=int(shp["plq_degree"]),
                             wlq_degree=int(shp["wlq_degree"]),
                             plq_on_device=True, wlq_on_device=False,
                             batch_len=int(shp["batch_len"]),
                             flush_rows=int(shp["flush_rows"]),
                             use_resident=True, name="sky_pf_tpu"))
            .chain_sink(Sink(sink_fn, vectorized=True)))


def result_table(rows):
    """The sink's rows under the reference's column names; ``_row`` is each
    result's row among the sink's.  (The harness compares whole numbers: a
    checksum is one, the sum of a few dozen grid coordinates.)"""
    return {"key": rows["key"], "wid": rows["id"], "size": rows["size"],
            "checksum": rows["checksum"], "ts": rows["ts"],
            "_row": np.arange(len(rows))}


def result_event_time_us(rows):
    """When a window's last event was due: its end (the result's ``ts`` is
    the window's last microsecond, which its last pane's result carries)."""
    return rows["ts"] + 1
