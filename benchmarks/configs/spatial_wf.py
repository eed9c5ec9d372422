"""Builds the ``spatial_wf`` deployment through ``MultiPipe`` and the public
patterns, from the sizes in ``spatial_wf.json``.  The only file of this
configuration that imports the program.

The window function is the deployment's own, kept here as the reference
keeps ``skytree.hpp`` beside ``test_spatial_wf.cpp``: the skyline of a
window's points by the all-pairs dominance test, written against the
library's device contract for a user's function
(``JaxWindowFunction``: ``fn(keys, gwids, cols, mask)`` over ``(B, pad)``
gathers of the window's rows)."""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from windflow_tpu.api import MultiPipe
from windflow_tpu.core.tuples import Schema
from windflow_tpu.core.windows import WinType
from windflow_tpu.patterns.basic import Sink, Source
from windflow_tpu.patterns.win_seq_tpu import JaxWindowFunction, WinFarmTPU

SCHEMA = Schema(x=np.float32, y=np.float32)
RESULT_FIELDS = {"size": np.int64, "checksum": np.float64}


def skyline(keys, gwids, cols, mask):
    """``size`` and ``checksum`` of each window's skyline (minimisation in
    both coordinates): point ``j`` dominates point ``i`` iff ``x_j <= x_i``,
    ``y_j <= y_i`` and one of them strictly, so identical points leave each
    other alive.  ``cols`` are ``(B, pad)``; ``mask`` says which cells hold a
    point of the window."""
    x, y = cols["x"], cols["y"]
    xi, yi = x[:, :, None], y[:, :, None]             # the point judged
    xj, yj = x[:, None, :], y[:, None, :]             # ... against every j
    dominated = ((xj <= xi) & (yj <= yi) & ((xj < xi) | (yj < yi))
                 & mask[:, None, :])
    alive = mask & ~jnp.any(dominated, axis=2)
    size = jnp.sum(alive, axis=1)
    checksum = jnp.sum(jnp.where(alive, x + y, 0.0), axis=1)
    return size, checksum


def window_function(ring_dtype=np.float32):
    """The skyline as the library takes a user's device function: its two
    coordinate rings in ``ring_dtype`` (the configuration's ``precision``:
    float32)."""
    return JaxWindowFunction(skyline, fields=("x", "y"),
                             result_fields=dict(RESULT_FIELDS),
                             field_dtypes={"x": ring_dtype, "y": ring_dtype})


def record_dtype(cfg):
    return SCHEMA.dtype()


def window_workers(cfg):
    return int(cfg["shapes"]["pardegree"])


def build(cfg, source_fn, sink_fn, trace_dir=None, name="spatial_wf"):
    shp = cfg["shapes"]
    return (MultiPipe(name, trace_dir=trace_dir)
            .add_source(Source(source_fn, SCHEMA, name="sq_gen", fresh=True))
            .add(WinFarmTPU(window_function(), int(shp["win_us"]),
                            int(shp["slide_us"]), WinType[shp["win_type"]],
                            pardegree=int(shp["pardegree"]),
                            batch_len=int(shp["batch_len"]),
                            flush_rows=int(shp["flush_rows"]),
                            use_resident=True, name="sky_wf_tpu"))
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
    the window's last microsecond).  The wait counted from here holds the
    rest of the chunk that carries the closing event."""
    return rows["ts"] + 1
