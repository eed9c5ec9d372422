"""Env-gated phase timers for the device ship path (``WF_PROFILE=1``).

A launch spends its time in host bookkeeping, ``device_put`` staging,
dispatch and harvest blocking; these timers split it so.  Timers are process-wide and near-free when
disabled; ``report()`` returns {phase: (seconds, calls)} and ``counters()``
plain accumulators (bytes shipped, launches, rows).

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

import os
import threading
import time
from collections import defaultdict

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

#: per-exit observer hook (obs/trace.py): called as ``fn(name, dt_ns)``
#: after every completed span, INDEPENDENTLY of the WF_PROFILE
#: accumulators — the bridge that turns the ship-path phase spans
#: (device_put / dispatch / harvest_wait, ops/resident.py) into
#: child spans of a traced batch.  One recorder per process; None
#: (default) keeps the probe a bare global read.
_RECORDER = None


def set_recorder(fn):
    """Install the span-exit observer (``fn(name, dt_ns)``).  The
    recorder must be cheap and must not raise — it runs inside the
    device ship hot path.  Installing one makes every span stamp its
    clock even with profiling disabled; pass ``None`` to uninstall."""
    global _RECORDER
    _RECORDER = fn


class span:
    """``with span("device_put"): ...`` — accumulates wall time per phase."""

    __slots__ = ("name", "t0", "_acc_on")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        # the span brackets ONE decision per sink: __exit__ accumulates
        # iff _acc_on, and calls the recorder iff t0 was stamped while
        # one was installed — a mid-span toggle cannot read a stale t0
        self._acc_on = _enabled()
        self.t0 = (time.perf_counter_ns()
                   if (self._acc_on or _RECORDER is not None) else None)
        return self

    def __exit__(self, *exc):
        if self.t0 is not None:
            dt_ns = time.perf_counter_ns() - self.t0
            if self._acc_on:
                with _mu:
                    _acc[self.name] += dt_ns / 1e9
                    _cnt[self.name] += 1
            rec = _RECORDER
            if rec is not None:
                rec(self.name, dt_ns)
        return False


def add(name: str, value: float = 1.0):
    """Accumulate a plain counter (bytes, rows, launches).  Reads the
    cached ENABLED mirror — a bare global, the cheapest possible disabled
    path — so an env toggle reaches add() at the next span entry (spans
    and adds interleave per shipped block, so staleness is one block)."""
    if ENABLED:
        with _mu:
            _val[name] += value


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


def reset():
    with _mu:
        _acc.clear()
        _cnt.clear()
        _val.clear()


def dump() -> str:
    lines = ["phase                      seconds    calls"]
    for k, (s, c) in report().items():
        lines.append(f"{k:<25} {s:>9.3f} {c:>8d}")
    for k, v in counters().items():
        lines.append(f"{k:<25} {v:>14.0f}")
    return "\n".join(lines)
