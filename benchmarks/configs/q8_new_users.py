"""Builds the ``q8_new_users`` deployment through ``MultiPipe`` and the
public patterns, from the sizes in ``q8_new_users.json``.  The only file of
this configuration that imports the program."""

from __future__ import annotations

import time

import numpy as np

from windflow_tpu.api import MultiPipe
from windflow_tpu.core.tuples import Schema
from windflow_tpu.core.windows import WinType
from windflow_tpu.patterns.basic import Filter, Sink, Source
from windflow_tpu.patterns.win_join_tpu import WinJoinTPU

from . import q8_new_users_oracle as oracle


def _schema(cfg):
    shp = cfg["shapes"]
    return Schema(event_type=np.int8, auction=np.int64, bidder=np.int64,
                  price=np.int64, person=np.int64, seller=np.int64,
                  reserve=np.int64,
                  extra=np.dtype((np.uint8, (int(shp["extra_bytes"]),))))


def record_dtype(cfg):
    dt = _schema(cfg).dtype()
    assert dt.itemsize == int(cfg["shapes"]["record_bytes"]), dt.itemsize
    return dt


def window_workers(cfg):
    """The device window workers: the join's one."""
    return int(cfg["shapes"]["join_degree"])


class _RunOn:
    """The harness's generator with person ids, sellers and auction ids
    running on.  The generator runs only ``id`` on from cycle to cycle
    (``harness/generator.py``), and this deployment joins on the person, so
    every chunk passes through the reference's ``run_on`` between the
    generator and the program's ``Source``.  That time is the load
    generator's: it lies inside the generator's push, and is printed when
    the stream ends."""

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
        print(f"{self.name}: person and auction ids run on in {self.chunks} "
              f"chunks, {self.seconds:.3f} s of the load generator's time "
              f"(inside its push; "
              f"{1e3 * self.seconds / max(self.chunks, 1):.3f} ms a chunk)",
              flush=True)


def build(cfg, source_fn, sink_fn, trace_dir=None, name="q8_new_users"):
    shp = cfg["shapes"]
    person, auction = int(shp["person_type"]), int(shp["auction_type"])
    id_range = tuple(int(v) for v in shp["id_range"])
    join = WinJoinTPU(
        int(shp["win_us"]), int(shp["slide_us"]), WinType[shp["win_type"]],
        side_field="event_type", left=(person, "person"),
        right=(auction, "seller"), key_range=id_range,
        right_fields=("auction", "reserve"),
        field_ranges={"auction": id_range,
                      "reserve": tuple(int(v) for v in shp["reserve_range"])},
        window_rows=int(shp["window_rows"]),
        max_results=int(shp["max_results"]),
        flush_rows=int(shp["flush_rows"]), name="q8_join")
    return (MultiPipe(name, trace_dir=trace_dir)
            .add_source(Source(_RunOn(cfg, source_fn, name), _schema(cfg),
                               parallelism=int(shp["sources"]),
                               name="q8_source"))
            .chain(Filter(lambda b: (b["event_type"] == person)
                          | (b["event_type"] == auction), vectorized=True,
                          name="q8_new"))
            .add(join)
            .add_sink(Sink(sink_fn, vectorized=True, name="q8_sink")))


def result_table(rows):
    """The sink's rows under the reference's column names: ``key`` the
    auction's id (a result is one auction of one window), ``person`` the
    join key; ``_row`` is each result's row among the sink's."""
    return {"key": rows["auction"], "wid": rows["id"], "person": rows["key"],
            "reserve": rows["reserve"], "ts": rows["ts"],
            "_row": np.arange(len(rows))}


def result_event_time_us(rows):
    """The event time of the last event contributing to a result's window:
    the latest ``ts`` among the window's results (a result carries the later
    of its two rows' times, and nearly every auction of the window's last
    chunk matches).  A result waits for the chunk that closes its window,
    the join step, the copy back and its piece's turn in the unpacking."""
    if not len(rows):
        return np.zeros(0, dtype=np.int64)
    wid = rows["id"]                     # window by window, as they arrived
    first = np.concatenate(([0], np.flatnonzero(np.diff(wid)) + 1))
    last = np.maximum.reduceat(rows["ts"], first)
    return np.repeat(last, np.diff(np.append(first, len(rows))))
