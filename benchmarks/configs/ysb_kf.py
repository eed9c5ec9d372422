"""Builds the ``ysb_kf`` deployment through ``MultiPipe`` and the public
patterns, from the sizes in ``ysb_kf.json``.  The only file of this
configuration that imports the program."""

from __future__ import annotations

import numpy as np

from windflow_tpu.api import MultiPipe
from windflow_tpu.core.tuples import Schema
from windflow_tpu.core.windows import WinType
from windflow_tpu.ops.functions import MultiReducer, Reducer
from windflow_tpu.patterns.basic import Filter, Map, Sink, Source
from windflow_tpu.patterns.win_seq_tpu import KeyFarmTPU

EVENT_SCHEMA = Schema(ad_id=np.int64, event_type=np.int8, revenue=np.int64)
JOINED_SCHEMA = Schema(revenue=np.int64)


def record_dtype(cfg):
    return EVENT_SCHEMA.dtype()


def window_workers(cfg):
    return int(cfg["shapes"]["pardegree"])


def build(cfg, source_fn, sink_fn, trace_dir=None, name="ysb_kf"):
    shp = cfg["shapes"]
    n_ads = int(shp["n_campaigns"]) * int(shp["ads_per_campaign"])
    ad_to_cmp = np.arange(n_ads) // int(shp["ads_per_campaign"])
    view = int(shp["view_type"])

    def join(b, out):
        out["key"] = ad_to_cmp[b["ad_id"]]
        out["revenue"] = b["revenue"]

    # event times are microseconds since the window opened: the declared
    # range proves the int32 MAX exact for runs under ~35 minutes
    agg = MultiReducer(
        Reducer("count", out_field="count"),
        Reducer("max", "ts", "lastUpdate", value_range=(0, 2_100_000_000)),
        Reducer("sum", "revenue", "revenue",
                value_range=(0, int(shp["revenue_modulus"]) + 1)))
    return (MultiPipe(name, trace_dir=trace_dir)
            .add_source(Source(source_fn, EVENT_SCHEMA,
                               parallelism=int(shp["sources"]),
                               name="ysb_source"))
            .chain(Filter(lambda b: b["event_type"] == view, vectorized=True,
                          name="ysb_filter"))
            .chain(Map(join, vectorized=True, output_schema=JOINED_SCHEMA,
                       name="ysb_join"))
            .add(KeyFarmTPU(agg, int(shp["win_us"]), int(shp["slide_us"]),
                            WinType[shp["win_type"]],
                            pardegree=int(shp["pardegree"]),
                            batch_len=int(shp["batch_len"]),
                            name="ysb_kf_tpu"))
            .chain_sink(Sink(sink_fn, vectorized=True, name="ysb_sink")))


def result_table(rows):
    """The sink's rows under the reference's column names; windows without a
    view carry no result; ``_row`` is each result's row among the sink's."""
    keep = np.flatnonzero(rows["count"] > 0)
    live = rows[keep]
    return {"key": live["key"], "wid": live["id"], "count": live["count"],
            "lastUpdate": live["lastUpdate"], "revenue": live["revenue"],
            "_row": keep}


def result_event_time_us(rows):
    """Event time of the last event contributing to each result."""
    return rows["lastUpdate"]
