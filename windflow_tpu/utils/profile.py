"""Env-gated phase timers for the device ship path (``WF_PROFILE=1``).

A launch spends its time in host bookkeeping, ``device_put`` staging,
dispatch and harvest blocking; these timers split it so.  Timers are process-wide and near-free when
disabled; ``report()`` returns {phase: (seconds, calls)} and ``counters()``
plain accumulators (bytes shipped, launches, rows).

When enabled a span is recorded three ways from ONE pair of clock reads:
the accumulators above; a ``jax.profiler.TraceAnnotation("wf.<phase>",
launch=, shard=)`` held open for the span's life, so the phase lies on the
host plane of the profiler's own trace, on the device events' clock; and a
record ``(phase, t0_ns, t1_ns, launch, shard, cause, extra, cpu_ns)`` in a
bounded ring (``records()``, ``write_records()`` — the engine writes its
own run's to ``<trace_dir>/launches.jsonl``).  ``cpu_ns`` is the span on
the thread's CPU clock, read at the same two instants: the rest of the span
the thread was not running (it waited, for the interpreter lock or anything
else); where that clock is dear every ``tracing.cpu_every()``-th span of a
phase has it, the others ``None`` and no field in the file.  ``launch`` is
the id ``next_id()`` gave the launch when the ship thread took it, ``cause``
the id of the ``_process_rows`` call that last fed its core.

Enablement is *not* frozen at import: ``WF_PROFILE`` is re-read lazily at
every ``span`` entry (spans bracket ms-scale ship phases, so the environ
lookup is noise there), and the parsed value is cached so ``add()`` —
the per-block hot probe — pays only a bare global read.  A test that
monkeypatches the environment, or a live session toggling telemetry
alongside ``wf_top``, thus takes effect without re-importing the module
(for ``add()``: at the next span entry).  ``enable()`` / ``disable()``
pin the state explicitly (and stop the env reads entirely); ``auto()``
returns to env-driven behavior.  The module-level ``ENABLED`` mirror is
kept for introspection and refreshed by every span entry.
"""

from __future__ import annotations

import itertools
import json
import os
import threading
import time
from collections import defaultdict, deque

from .tracing import cpu_every

_FORCED: bool | None = None   # enable()/disable() override; None = env


_env_raw = object()       # last seen WF_PROFILE string (sentinel: never)
_env_parsed = False


def _env_enabled() -> bool:
    # probe cost must stay near the old module-global read: one environ
    # lookup plus a short-string compare (os.environ.get decodes a fresh
    # str per call, so identity can't be used); the int() parse runs
    # only when the variable actually changed
    global _env_raw, _env_parsed
    raw = os.environ.get("WF_PROFILE")
    if raw != _env_raw:
        _env_parsed = bool(int(raw or "0"))
        _env_raw = raw
    return _env_parsed


#: introspection mirror of the last observed state (back-compat with the
#: historical import-time constant); the source of truth is _enabled()
ENABLED = _env_enabled()


def _enabled() -> bool:
    global ENABLED
    if _FORCED is None:
        ENABLED = _env_enabled()
    return ENABLED


def enable():
    """Pin profiling ON regardless of WF_PROFILE (until auto())."""
    global _FORCED, ENABLED
    _FORCED = ENABLED = True


def disable():
    """Pin profiling OFF regardless of WF_PROFILE (until auto())."""
    global _FORCED, ENABLED
    _FORCED = ENABLED = False


def auto():
    """Drop any enable()/disable() pin: follow WF_PROFILE again."""
    global _FORCED, ENABLED
    _FORCED = None
    ENABLED = _env_enabled()

_acc: dict[str, float] = defaultdict(float)
_cnt: dict[str, int] = defaultdict(int)
_val: dict[str, float] = defaultdict(float)
#: ship threads (one per shard) enter the same spans concurrently; the
#: read-add-store on the accumulators must not lose updates
_mu = threading.Lock()

#: the spans of the run, oldest dropped first: (phase, t0_ns, t1_ns,
#: launch, shard, cause, extra, cpu_ns) on the perf_counter_ns clock.
#: Sized for a benchmark run (a few hundred launches and chunks a second
#: for a minute)
_RING = deque(maxlen=1 << 17)

#: how far back ``amend`` looks: a record is amended milliseconds after it
#: is written, a few dozen spans later
_AMEND_DEPTH = 1 << 12

#: one id sequence for launches and for the bookkeeping calls that cause
#: them, so an id orders what it names and a cause is always smaller than
#: the launches it fed (next() on a count is atomic under the GIL)
_ids = itertools.count(1)


def next_id() -> int:
    return next(_ids)


#: per-exit observer hook (obs/trace.py): called as ``fn(name, dt_ns)``
#: — ``fn(name, dt_ns, launch)`` for a span that names its launch —
#: after every completed span, INDEPENDENTLY of the WF_PROFILE
#: accumulators — the bridge that turns the ship-path phase spans
#: (device_put / dispatch / harvest_wait, ops/resident.py) into
#: child spans of a traced batch.  One recorder per process; None
#: (default) keeps the probe a bare global read.
_RECORDER = None


def set_recorder(fn):
    """Install the span-exit observer (``fn(name, dt_ns[, launch])``).
    The recorder must be cheap and must not raise — it runs inside the
    device ship hot path.  Installing one makes every span stamp its
    clock even with profiling disabled; pass ``None`` to uninstall."""
    global _RECORDER
    _RECORDER = fn


_annotation_cls = None


def _annotation(name, launch, shard):
    """An entered ``TraceAnnotation`` (a no-op costing one atomic read
    while no profiler session runs)."""
    global _annotation_cls
    if _annotation_cls is None:
        from jax.profiler import TraceAnnotation
        _annotation_cls = TraceAnnotation
    kw = {}
    if launch is not None:
        kw["launch"] = launch
    if shard is not None:
        kw["shard"] = shard
    ann = _annotation_cls("wf." + name, **kw)
    ann.__enter__()
    return ann


class span:
    """``with span("device_put", launch=7, shard=0): ...`` — one ship
    phase: wall time per phase, and with profiling on a ``wf.`` annotation
    and a ring record with the span's CPU time (module docstring).
    ``extra`` may be set inside the block (a dict) and rides on the
    record."""

    __slots__ = ("name", "launch", "shard", "cause", "extra", "t0", "t1",
                 "_c0", "_acc_on", "_ann")

    def __init__(self, name: str, launch: int = None, shard: int = None,
                 cause: int = None):
        self.name = name
        self.launch = launch
        self.shard = shard
        self.cause = cause
        self.extra = None
        self.t1 = None

    def __enter__(self):
        # the span brackets ONE decision per sink: __exit__ accumulates
        # iff _acc_on, and calls the recorder iff t0 was stamped while
        # one was installed — a mid-span toggle cannot read a stale t0
        self._acc_on = _enabled()
        if self._acc_on:
            self._ann = _annotation(self.name, self.launch, self.shard)
            self.t0 = time.perf_counter_ns()
            # every cpu_every()-th span of a phase, counted as they close
            self._c0 = (time.thread_time_ns()
                        if _cnt.get(self.name, 0) % cpu_every() == 0
                        else None)
        else:
            self.t0 = (time.perf_counter_ns()
                       if _RECORDER is not None else None)
        return self

    def __exit__(self, *exc):
        t0 = self.t0
        if t0 is not None:
            t1 = self.t1 = time.perf_counter_ns()
            if self._acc_on:
                cpu = (None if self._c0 is None
                       else time.thread_time_ns() - self._c0)
                self._ann.__exit__(*exc)
                with _mu:
                    _acc[self.name] += (t1 - t0) / 1e9
                    _cnt[self.name] += 1
                    _RING.append((self.name, t0, t1, self.launch,
                                  self.shard, self.cause, self.extra, cpu))
            rec = _RECORDER
            if rec is not None:
                if self.launch is None:
                    rec(self.name, t1 - t0)
                else:
                    rec(self.name, t1 - t0, self.launch)
        return False

    def end_ns(self) -> int:
        """The clock at this span's exit: the stamp the span took if it
        timed itself, read now otherwise — so a launch boundary that is
        also a span's edge is stamped once."""
        return self.t1 if self.t1 is not None else time.perf_counter_ns()


def add(name: str, value: float = 1.0):
    """Accumulate a plain counter (bytes, rows, launches).  Reads the
    cached ENABLED mirror — a bare global, the cheapest possible disabled
    path — so an env toggle reaches add() at the next span entry (spans
    and adds interleave per shipped block, so staleness is one block)."""
    if ENABLED:
        with _mu:
            _val[name] += value


def record(name: str, seconds: float, calls: int = 1):
    """Account time measured elsewhere (inside a native call) to the span
    `name`: the accumulators only -- no annotation, no ring record."""
    if ENABLED:
        with _mu:
            _acc[name] += seconds
            _cnt[name] += calls


def amend(phase: str, launch: int, since_end=None, **fields):
    """Add `fields` to the newest record of `phase` that names `launch` and
    carries extra fields — what became of the span's result after it
    closed.  ``since_end=(name, t_ns)`` adds under `name` the milliseconds
    from the record's end to `t_ns`.  Nothing happens if the ring holds no
    such record among its newest ``_AMEND_DEPTH``."""
    if not ENABLED:
        return
    with _mu:
        for rec in itertools.islice(reversed(_RING), _AMEND_DEPTH):
            if rec[3] == launch and rec[0] == phase and rec[6] is not None:
                if since_end is not None:
                    name, t_ns = since_end
                    fields[name] = round((t_ns - rec[2]) / 1e6, 4)
                rec[6].update(fields)
                return


def report() -> dict:
    # snapshot under the lock: ship threads mutate the defaultdicts
    # concurrently, and iterating a dict mid-resize raises "dictionary
    # changed size during iteration"
    with _mu:
        acc = dict(_acc)
        cnt = dict(_cnt)
    return {k: (round(acc[k], 4), cnt[k]) for k in sorted(acc)}


def counters() -> dict:
    with _mu:
        val = dict(_val)
    return {k: val[k] for k in sorted(val)}


def records() -> list:
    """The ring's spans, oldest first (a snapshot)."""
    with _mu:
        return list(_RING)


def write_records(path: str, since_ns: int = None) -> int:
    """Write the ring as one JSON object per span; returns how many.
    With ``since_ns`` only the spans that began at or after it (a graph
    writes its own run's, not what an earlier one left in the ring).
    Nothing to write, nothing written."""
    recs = records()
    if since_ns is not None:
        recs = [r for r in recs if r[1] >= since_ns]
    if not recs:
        return 0
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        for phase, t0, t1, launch, shard, cause, extra, cpu in recs:
            line = {"phase": phase, "t0_ns": t0, "t1_ns": t1,
                    "launch": launch, "shard": shard, "cause": cause}
            if cpu is not None:
                line["cpu_ns"] = cpu
            if extra:
                line.update(extra)
            f.write(json.dumps(line) + "\n")
    return len(recs)


def reset():
    with _mu:
        _acc.clear()
        _cnt.clear()
        _val.clear()
        _RING.clear()
