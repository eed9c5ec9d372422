"""Builds the ``sum_cb`` deployment through ``MultiPipe`` and the public
patterns, from the sizes in ``sum_cb.json``.  The only file of this
configuration that imports the program."""

from __future__ import annotations

import numpy as np

from windflow_tpu.api import MultiPipe
from windflow_tpu.core.tuples import Schema
from windflow_tpu.core.windows import WinType
from windflow_tpu.ops.functions import Reducer
from windflow_tpu.patterns.basic import Sink, Source
from windflow_tpu.patterns.win_seq_tpu import WinSeqTPU

SCHEMA = Schema(value=np.int64)


def record_dtype(cfg):
    return SCHEMA.dtype()


def window_workers(cfg):
    return int(cfg["shapes"]["window_workers"])


def build(cfg, source_fn, sink_fn, trace_dir=None, name="sum_cb"):
    shp = cfg["shapes"]
    lo, hi = (int(x) for x in shp["value_range"])
    # the declared range proves the device path's int32 accumulate fits
    red = Reducer("sum", value_range=(lo, hi))
    return (MultiPipe(name, capacity=int(shp["capacity"]),
                      trace_dir=trace_dir)
            .add_source(Source(source_fn, SCHEMA, name="src", fresh=True))
            .add(WinSeqTPU(red, int(shp["win"]), int(shp["slide"]),
                           WinType[shp["win_type"]],
                           batch_len=int(shp["batch_len"]),
                           flush_rows=int(shp["flush_rows"]),
                           depth=int(shp["depth"]),
                           shards=int(shp["shards"])))
            .add_sink(Sink(sink_fn, vectorized=True)))


def result_table(rows):
    """The sink's rows under the reference's column names; ``_row`` is each
    result's row among the sink's."""
    return {"key": rows["key"], "wid": rows["id"], "value": rows["value"],
            "ts": rows["ts"], "_row": np.arange(len(rows))}


def result_event_time_us(rows):
    """Event time of the last event contributing to each result."""
    return rows["ts"]
