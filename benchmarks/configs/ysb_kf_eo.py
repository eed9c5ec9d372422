"""Builds the ``ysb_kf_eo`` deployment through ``MultiPipe`` and the public
patterns, from the sizes in ``ysb_kf_eo.json``: ``ysb_kf``'s pipeline under
``MultiPipe(recovery=)``, with the configuration's own fault.  The only file
of this configuration that imports the program.

The fault is not the library's: window worker ``kill.worker`` is a
``KeyFarmTPU`` replica whose ``svc`` this file wraps, as
``tests/test_recovery.py`` wraps a node to raise at its n-th call.  It raises
once, before the call does anything, in the first call whose batch holds an
event at or after the kill's event time -- event time is the generator's
clock in this stream, so the kill meets the same fill of the window in every
run.  The warm-up pass (``name="warmup"``) is killed at the same offset into
its first window.

A run whose fault did not fire, whose worker was not restored exactly once or
whose warm-up was not killed must not end in a ``correct`` line:
``result_table``, which the harness calls once the measured graph has joined,
raises instead.  (A graph that gave up raises from its own ``wait``.)"""

from __future__ import annotations

import numpy as np

from windflow_tpu.api import MultiPipe
from windflow_tpu.core.windows import WinType
from windflow_tpu.ops.functions import MultiReducer, Reducer
from windflow_tpu.patterns.basic import Filter, Map, Sink, Source
from windflow_tpu.patterns.win_seq_tpu import KeyFarmTPU
from windflow_tpu.recovery import RecoveryPolicy

from . import ysb_kf
from .ysb_kf import (EVENT_SCHEMA, JOINED_SCHEMA, record_dtype,  # noqa: F401
                     result_event_time_us, window_workers)

#: the passes this process built ("warmup" / "measured"): the fault each
#: carries and the pipe's own report of what its recovery layer did -- the
#: bound method while the pass may still run, its result once it was asked
#: for (the method holds the whole graph, rings and export buffers included)
_BUILT = {}


class InjectedCrash(RuntimeError):
    """The configuration's fault: a window worker dies in mid-stream."""


class _Fault:
    """Raises once in ``svc``, before the wrapped call, at the first batch
    that holds an event at or after ``at_us``.  With ``after_emit`` it waits
    for such a call to have emitted results (a launch that carried closed
    windows) and raises in the call after it, so that the replay re-emits
    them: the delivery control's kill."""

    def __init__(self, worker, at_us, after_emit=False):
        self.worker, self.at_us = int(worker), int(at_us)
        self.after_emit = bool(after_emit)
        self.fired_at_us = None
        self.node_name = None
        self._emitted = 0
        self._armed = not self.after_emit

    def arm(self, node):
        inner = node.svc
        self.node_name = node.name
        if self.after_emit:
            emit = node.emit

            def counting_emit(batch):
                self._emitted += 1
                return emit(batch)

            node.emit = counting_emit

        def svc(batch, channel=0):
            if (self.fired_at_us is None and len(batch)
                    and int(batch["ts"].max()) >= self.at_us):
                if self._armed:
                    self.fired_at_us = int(batch["ts"].max())
                    raise InjectedCrash(
                        f"{node.name}: injected crash at event time "
                        f"{self.fired_at_us} us (due from {self.at_us})")
                before = self._emitted
                inner(batch, channel)
                self._armed = self._emitted > before
                return
            inner(batch, channel)

        node.svc = svc


class _KillableKeyFarm(KeyFarmTPU):
    """``KeyFarmTPU`` whose replica ``fault.worker`` carries the fault."""

    def __init__(self, *args, fault, **kw):
        super().__init__(*args, **kw)
        self._fault = fault

    def _make_replica(self, i):
        node = super()._make_replica(i)
        if i == self._fault.worker:
            self._fault.arm(node)
        return node


def kill_time_us(cfg, warmup):
    """Event time of the kill: ``offset_us`` into the window the pass names."""
    kill = cfg["kill"]
    index = kill["warmup_window_index"] if warmup else kill["window_index"]
    return int(index) * int(cfg["shapes"]["win_us"]) + int(kill["offset_us"])


def build(cfg, source_fn, sink_fn, trace_dir=None, name="ysb_kf_eo"):
    shp, kill = cfg["shapes"], cfg["kill"]
    n_ads = int(shp["n_campaigns"]) * int(shp["ads_per_campaign"])
    ad_to_cmp = np.arange(n_ads) // int(shp["ads_per_campaign"])
    view = int(shp["view_type"])
    warmup = name == "warmup"
    if not warmup:
        _require_fired("warmup")

    def join(b, out):
        out["key"] = ad_to_cmp[b["ad_id"]]
        out["revenue"] = b["revenue"]

    policy = RecoveryPolicy(**{k: v for k, v in cfg["recovery"].items()
                               if k != "why"})
    fault = _Fault(kill["worker"], kill_time_us(cfg, warmup),
                   kill.get("after_emit", False) and not warmup)
    agg = MultiReducer(
        Reducer("count", out_field="count"),
        Reducer("max", "ts", "lastUpdate", value_range=(0, 2_100_000_000)),
        Reducer("sum", "revenue", "revenue",
                value_range=(0, int(shp["revenue_modulus"]) + 1)))
    pipe = (MultiPipe(name, trace_dir=trace_dir, recovery=policy)
            .add_source(Source(source_fn, EVENT_SCHEMA,
                               parallelism=int(shp["sources"]),
                               name="ysb_source"))
            .chain(Filter(lambda b: b["event_type"] == view, vectorized=True,
                          name="ysb_filter"))
            .chain(Map(join, vectorized=True, output_schema=JOINED_SCHEMA,
                       name="ysb_join"))
            .add(_KillableKeyFarm(agg, int(shp["win_us"]),
                                  int(shp["slide_us"]),
                                  WinType[shp["win_type"]],
                                  pardegree=int(shp["pardegree"]),
                                  batch_len=int(shp["batch_len"]),
                                  flush_rows=int(cfg["ship"]["flush_rows"]),
                                  name="ysb_kf_tpu", fault=fault))
            .chain_sink(Sink(sink_fn, vectorized=True, name="ysb_sink")))
    # (a program without the report cannot run this configuration: it fails
    # here, as the warm-up pass is built)
    _BUILT["warmup" if warmup else "measured"] = [fault, pipe.recovery_report]
    return pipe


def _require_fired(which):
    """The pass's fault fired and its worker was restored exactly once;
    asked for once the pass is over, so the graph is let go of here."""
    if which not in _BUILT:
        raise RuntimeError(f"ysb_kf_eo: no {which} pass was built")
    fault, report = _BUILT[which]
    if callable(report):
        report = _BUILT[which][1] = report()
    if fault.fired_at_us is None:
        raise RuntimeError(
            f"ysb_kf_eo: the {which} pass's fault never fired: no batch of "
            f"worker {fault.worker} reached event time {fault.at_us} us")
    mine = [c for node, c in report.items() if node.endswith(fault.node_name)]
    if len(mine) != 1 or mine[0]["node_restarts"] != 1:
        raise RuntimeError(
            f"ysb_kf_eo: the {which} pass's worker {fault.worker} was not "
            f"restored exactly once: {mine}")
    return fault, mine[0]


def fault_report(which="measured"):
    """``(event time the fault fired at, the killed worker's recovery
    counters, every supervised node's)`` of a pass, for the tests and the
    controls."""
    fault, counters = _require_fired(which)
    return fault.fired_at_us, counters, _BUILT[which][1]


def result_table(rows):
    """``ysb_kf``'s table -- once the fault is known to have fired and the
    worker to have been restored, else the run fails here."""
    _require_fired("measured")
    return ysb_kf.result_table(rows)
