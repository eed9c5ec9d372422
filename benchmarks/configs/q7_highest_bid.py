"""Builds the ``q7_highest_bid`` deployment through ``MultiPipe`` and the
public patterns, from the sizes in ``q7_highest_bid.json``.  The only file of
this configuration that imports the program."""

from __future__ import annotations

import numpy as np

from windflow_tpu.api import MultiPipe
from windflow_tpu.core.tuples import Schema
from windflow_tpu.core.windows import WinType
from windflow_tpu.ops.functions import ArgReducer, MultiReducer, Reducer
from windflow_tpu.patterns.basic import Filter, Sink, Source
from windflow_tpu.patterns.win_seq_tpu import WinMapReduceTPU


def _schema(cfg):
    shp = cfg["shapes"]
    return Schema(event_type=np.int8, auction=np.int64, bidder=np.int64,
                  price=np.int64,
                  extra=np.dtype((np.uint8, (int(shp["extra_bytes"]),))))


def record_dtype(cfg):
    dt = _schema(cfg).dtype()
    assert dt.itemsize == int(cfg["shapes"]["record_bytes"]), dt.itemsize
    return dt


def window_workers(cfg):
    shp = cfg["shapes"]
    return int(shp["map_degree"]) + int(shp["reduce_degree"])


def build(cfg, source_fn, sink_fn, trace_dir=None, name="q7_highest_bid"):
    shp = cfg["shapes"]
    bid = int(shp["bid_type"])
    price_range = tuple(int(v) for v in shp["price_range"])
    # event times are microseconds since the window opened: the declared
    # range proves the int32 MAX exact for runs under ~35 minutes
    ts_range = (0, 2_100_000_000)
    # MAP: the highest bid of this worker's quarter of the window, with its
    # id for the REDUCE stage's tie-break; COUNT and MAX(ts) are free on the
    # host, so only the price ships
    map_fn = MultiReducer(
        ArgReducer("max", "price", id_out="bid",
                   carry=("auction", "bidder", ("ts", "dateTime")),
                   value_range=price_range,
                   window_rows=int(shp["map_window_rows"])),
        Reducer("count", out_field="count"),
        Reducer("max", "ts", "lastUpdate", value_range=ts_range))
    # REDUCE: the same monoid over the partials, the counts summed
    reduce_fn = MultiReducer(
        ArgReducer("max", "price", id_field="bid",
                   carry=("auction", "bidder", "dateTime"),
                   value_range=price_range),
        Reducer("sum", "count", "count",
                value_range=(0, int(shp["partial_count_max"]))),
        Reducer("max", "lastUpdate", "lastUpdate", value_range=ts_range))
    schema = _schema(cfg)
    return (MultiPipe(name, trace_dir=trace_dir)
            .add_source(Source(source_fn, schema,
                               parallelism=int(shp["sources"]),
                               name="q7_source"))
            .chain(Filter(lambda b: b["event_type"] == bid, vectorized=True,
                          name="q7_bids"))
            .add(WinMapReduceTPU(
                map_fn, reduce_fn, int(shp["win_us"]), int(shp["slide_us"]),
                WinType[shp["win_type"]],
                map_degree=int(shp["map_degree"]),
                reduce_degree=int(shp["reduce_degree"]),
                map_on_device=True, reduce_on_device=True,
                batch_len=int(shp["batch_len"]),
                flush_rows=int(shp["flush_rows"]), name="q7_wmr"))
            .chain_sink(Sink(sink_fn, vectorized=True, name="q7_sink")))


def result_table(rows):
    """The sink's rows under the reference's column names; windows without a
    bid carry no result; ``_row`` is each result's row among the sink's."""
    keep = np.flatnonzero(rows["count"] > 0)
    live = rows[keep]
    return {"key": live["key"], "wid": live["id"], "auction": live["auction"],
            "bidder": live["bidder"], "price": live["price"],
            "dateTime": live["dateTime"], "count": live["count"],
            "lastUpdate": live["lastUpdate"], "_row": keep}


def result_event_time_us(rows):
    """Event time of the last event contributing to each result."""
    return rows["lastUpdate"]
