"""Builds the ``q5_hot_items`` deployment through ``MultiPipe`` and the
public patterns, from the sizes in ``q5_hot_items.json``.  The only file of
this configuration that imports the program."""

from __future__ import annotations

import time

import numpy as np

from windflow_tpu.api import MultiPipe
from windflow_tpu.core.tuples import Schema
from windflow_tpu.core.windows import WinType
from windflow_tpu.ops.functions import ArgReducer, MultiReducer, Reducer
from windflow_tpu.patterns.basic import Filter, Map, Sink, Source
from windflow_tpu.patterns.win_seq_tpu import KeyFarmTPU, WinSeqTPU

from . import q5_hot_items_oracle as oracle

#: what stage 1 hands stage 2: an auction's bids in one window, twice (the
#: arg-max runs over ``num``, the window's total over ``bids``: a ring each)
COUNTS = Schema(auction=np.int64, num=np.int64, bids=np.int64,
                lastUpdate=np.int64)


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
    """The device window workers: stage 2's one."""
    return int(cfg["shapes"]["top_degree"])


class _RunOn:
    """The harness's generator with auction ids running on.  The generator
    runs only ``id`` on from cycle to cycle (``harness/generator.py``), and
    this deployment keys on the auction, so every chunk passes through the
    reference's ``run_on`` between the generator and the program's
    ``Source``.  That time is the load generator's: it lies inside the
    generator's push, and is printed when the stream ends."""

    def __init__(self, cfg, source_fn, name):
        self.cfg, self.source_fn, self.name = cfg, source_fn, name
        self.shipper = None
        self.seconds, self.chunks = 0.0, 0

    def push_batch(self, batch):
        t0 = time.perf_counter()
        oracle.run_on(self.cfg, batch)
        self.seconds += time.perf_counter() - t0
        self.chunks += 1
        self.shipper.push_batch(batch)

    def __call__(self, shipper):
        self.shipper = shipper
        self.source_fn(self)
        print(f"{self.name}: auction ids run on in {self.chunks} chunks, "
              f"{self.seconds:.3f} s of the load generator's time (inside "
              f"its push; {1e3 * self.seconds / max(self.chunks, 1):.3f} ms "
              f"a chunk)", flush=True)


def _to_counts(rows, out):
    """One key-less window of counts: key := 0, the auction becomes a field."""
    out["key"] = 0
    out["auction"] = rows["key"]
    out["num"] = rows["count"]
    out["bids"] = rows["count"]
    out["lastUpdate"] = rows["lastUpdate"]


def build(cfg, source_fn, sink_fn, trace_dir=None, name="q5_hot_items"):
    shp = cfg["shapes"]
    bid = int(shp["bid_type"])
    # event times are microseconds since the window opened: the declared
    # range proves the int32 MAX exact for runs under ~35 minutes
    ts_range = (0, 2_100_000_000)
    # stage 1: COUNT and MAX(ts) are free on the host, so the program keeps
    # the stage there (plan_core); it closes on the stream's time
    per_auction = MultiReducer(
        Reducer("count", out_field="count"),
        Reducer("max", "ts", "lastUpdate", value_range=ts_range))
    # stage 2: the auction with the most bids, ties to the lowest id, the
    # window's bids and its last bid's time
    hottest = MultiReducer(
        ArgReducer("max", "num", id_field="auction", id_out="auction",
                   value_range=(0, int(shp["count_max"])),
                   window_rows=int(shp["top_window_rows"])),
        Reducer("sum", "bids", "bids",
                value_range=(0, int(shp["count_max"]))),
        Reducer("max", "lastUpdate", "lastUpdate", value_range=ts_range))
    return (MultiPipe(name, trace_dir=trace_dir)
            .add_source(Source(_RunOn(cfg, source_fn, name), _schema(cfg),
                               parallelism=int(shp["sources"]),
                               name="q5_source"))
            .chain(Filter(lambda b: b["event_type"] == bid, vectorized=True,
                          name="q5_bids"))
            .add(KeyFarmTPU(per_auction, int(shp["win_us"]),
                            int(shp["slide_us"]), WinType[shp["win_type"]],
                            pardegree=int(shp["count_degree"]),
                            fire_on=shp["fire_on"],
                            flush_rows=int(shp["flush_rows"]),
                            name="q5_count"))
            .add(Map(_to_counts, vectorized=True, output_schema=COUNTS,
                     name="q5_rekey"))
            .add(WinSeqTPU(hottest, int(shp["slide_us"]),
                           int(shp["slide_us"]), WinType[shp["win_type"]],
                           batch_len=int(shp["top_batch_len"]),
                           flush_rows=int(shp["flush_rows"]),
                           name="q5_top"))
            .chain_sink(Sink(sink_fn, vectorized=True, name="q5_sink")))


def result_table(rows):
    """The sink's rows under the reference's column names.  Stage 2's window
    ``id`` holds the counts whose windows end in it: stage 1's window
    ``id - 1``, which is the reference's ``wid``.  A stage-2 window without a
    count (one a progress row opened at the stream's end) carries no result;
    ``_row`` is each result's row among the sink's."""
    keep = np.flatnonzero(rows["bids"] > 0)
    live = rows[keep]
    return {"key": live["key"], "wid": live["id"] - 1,
            "auction": live["auction"], "num": live["num"],
            "bids": live["bids"], "lastUpdate": live["lastUpdate"],
            "_row": keep}


def result_event_time_us(rows):
    """Event time of the last event contributing to each result."""
    return rows["lastUpdate"]
