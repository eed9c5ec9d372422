"""Builds the ``sum_tb_late`` deployment through ``MultiPipe`` and the public
patterns, from the sizes in ``sum_tb_late.json``.  The only file of this
configuration that imports the program."""

from __future__ import annotations

import numpy as np

from windflow_tpu.api import MultiPipe
from windflow_tpu.core.tuples import Schema
from windflow_tpu.core.windows import WinType
from windflow_tpu.ops.functions import Reducer
from windflow_tpu.patterns.basic import Sink, Source
from windflow_tpu.patterns.win_seq_tpu import KeyFarmTPU

from .sum_tb_late_oracle import wid_offset

SCHEMA = Schema(value=np.int64)
#: what the two result functions need of the configuration: the harness calls
#: them with the sink's rows alone, after ``build``
_BUILT = {}


def record_dtype(cfg):
    return SCHEMA.dtype()


def window_workers(cfg):
    return int(cfg["shapes"]["pardegree"])


def build(cfg, source_fn, sink_fn, trace_dir=None, name="sum_tb_late"):
    shp = cfg["shapes"]
    holdback = int(shp["holdback_us"])
    lo, hi = (int(x) for x in shp["value_range"])
    red = Reducer("sum", value_range=(lo, hi))
    pipe = (MultiPipe(name, capacity=int(shp["capacity"]),
                      trace_dir=trace_dir)
            .add_source(Source(source_fn, SCHEMA, name="src", fresh=True))
            .add(KeyFarmTPU(red, int(shp["win_us"]), int(shp["slide_us"]),
                            WinType[shp["win_type"]],
                            pardegree=int(shp["pardegree"]),
                            batch_len=int(shp["batch_len"]),
                            flush_rows=int(shp["flush_rows"]),
                            depth=int(shp["depth"]),
                            fire_on=shp["fire_on"], holdback=holdback,
                            name="sum_tb_kf"))
            .add_sink(Sink(sink_fn, vectorized=True)))
    # (the reference numbers windows from the first one an event can lie in)
    _BUILT.update(first_window=wid_offset(cfg), holdback=holdback)
    return pipe


def result_table(rows):
    """The sink's rows under the reference's column names: ``wid`` counts
    windows from the first one an event can lie in; ``_row`` is each result's
    row among the sink's."""
    return {"key": rows["key"], "wid": rows["id"] + _BUILT["first_window"],
            "value": rows["value"], "ts": rows["ts"],
            "_row": np.arange(len(rows))}


def result_event_time_us(rows):
    """When the watermark itself lets a result go: its window's end (the
    result's ``ts`` is the window's last microsecond) plus the hold-back.
    The latency counted from here is the wait beyond that."""
    return rows["ts"] + 1 + _BUILT["holdback"]
