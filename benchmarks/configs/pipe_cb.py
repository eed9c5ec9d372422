"""Builds the ``pipe_cb`` deployment through ``MultiPipe`` and the public
patterns, from the sizes in ``pipe_cb.json``.  The only file of this
configuration that imports the program."""

from __future__ import annotations

import numpy as np

from windflow_tpu.api import MultiPipe
from windflow_tpu.core.tuples import Schema
from windflow_tpu.core.windows import WinType
from windflow_tpu.ops.functions import Reducer
from windflow_tpu.patterns.basic import Filter, Map, Sink, Source
from windflow_tpu.patterns.win_seq_tpu import WinFarmTPU

SCHEMA = Schema(value=np.int64)


def record_dtype(cfg):
    return SCHEMA.dtype()


def window_workers(cfg):
    return int(cfg["shapes"]["pardegree"])


def build(cfg, source_fn, sink_fn, trace_dir=None, name="pipe_cb"):
    shp = cfg["shapes"]
    mul, add = (int(x) for x in shp["map"])
    drop = int(shp["filter_drop_multiple_of"])
    lo, hi = shp["value_range"]

    def transform_inplace(batch):
        v = batch["value"]
        np.multiply(v, mul, out=v)
        np.add(v, add, out=v)

    # values after the Map stay in [add, mul*hi + add): declared, so that the
    # device path's int32 accumulate is proved to fit
    red = Reducer("sum", value_range=(0, mul * int(hi) + add))
    return (MultiPipe(name, capacity=int(shp["capacity"]),
                      trace_dir=trace_dir)
            .add_source(Source(source_fn, SCHEMA, name="src", fresh=True))
            .chain(Map(transform_inplace, vectorized=True))
            .chain(Filter(lambda b: b["value"] % drop != 0, vectorized=True))
            .add(WinFarmTPU(red, int(shp["win"]), int(shp["slide"]),
                            WinType[shp["win_type"]],
                            pardegree=int(shp["pardegree"]),
                            batch_len=int(shp["batch_len"]),
                            flush_rows=int(shp["flush_rows"]),
                            depth=int(shp["depth"])))
            .chain_sink(Sink(sink_fn, vectorized=True)))


def result_table(rows):
    """The sink's rows under the reference's column names; ``_row`` is each
    result's row among the sink's."""
    return {"key": rows["key"], "wid": rows["id"], "value": rows["value"],
            "ts": rows["ts"], "_row": np.arange(len(rows))}


def result_event_time_us(rows):
    """Event time of the last event contributing to each result."""
    return rows["ts"]
