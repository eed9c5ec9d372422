"""Builds the fixture two-stage configuration ``count_sum`` (a test's, not
the benchmark's): a count per key, which the program keeps on the host, then a
sum over the counts on the device."""

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
    """The *device* window workers (the denominator of the span shares)."""
    return int(cfg["shapes"]["device_window_workers"])


def build(cfg, source_fn, sink_fn, trace_dir=None, name="count_sum"):
    shp = cfg["shapes"]
    kind = WinType[shp["win_type"]]
    count = Reducer("count", out_field="count")
    total = Reducer("sum", "count", "total",
                    value_range=(0, int(shp["count_win"]) + 1))
    return (MultiPipe(name, capacity=int(shp["capacity"]),
                      trace_dir=trace_dir)
            .add_source(Source(source_fn, SCHEMA, name="src", fresh=True))
            .add(WinSeqTPU(count, int(shp["count_win"]),
                           int(shp["count_slide"]), kind, name="count"))
            .add(WinSeqTPU(total, int(shp["sum_win"]), int(shp["sum_slide"]),
                           kind, batch_len=int(shp["batch_len"]),
                           flush_rows=int(shp["flush_rows"]), name="sum"))
            .add_sink(Sink(sink_fn, vectorized=True)))


def result_table(rows):
    return {"key": rows["key"], "wid": rows["id"], "total": rows["total"],
            "ts": rows["ts"], "_row": np.arange(len(rows))}


def result_event_time_us(rows):
    return rows["ts"]
