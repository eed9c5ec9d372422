"""Per-node tracing — the runtime-enabled equivalent of the reference's
compile-time ``-DLOG_DIR`` instrumentation (map.hpp:85-91,116-176,
win_seq.hpp:128-138,479-501, win_seq_gpu.hpp:175-185,598-611): every node
keeps received-batch/tuple counters, a running and EWMA service time, its
life split three ways — idle (waiting on its inbox), blocked (inside a
``put`` on the next node's inbox) and self (service minus blocked) — on
two clocks, the wall's and the thread's own CPU clock (the run's CPU less
its waits' is the node's own work's; wall minus CPU is the time the thread
was in it and not running), and (window nodes) the triggering vs
non-triggering split; at ``svc_end`` the counters are written to
``<dir>/<node_name>.log`` as one JSON object.  A source has one too: its
life is ``generate()``, and the stages fused into its thread
(runtime/comb.py) are timed per stage.

Enabled at runtime (no recompilation): pass ``trace_dir=`` to
:class:`~windflow_tpu.runtime.engine.Dataflow` / ``MultiPipe``, or set the
``WF_LOG_DIR`` environment variable (the spiritual ``-DLOG_DIR``).

These counters also feed the *live* observability layer: when the
dataflow runs with ``metrics=`` / ``sample_period=`` the engine creates a
``NodeStats`` per node even without a trace dir, and the background
sampler (obs/sampler.py) reads ``snapshot()``-equivalent fields racily
while the graph runs — end-of-run files stay gated on ``trace_dir``
alone, so the seed tracing behavior is unchanged.
"""

from __future__ import annotations

import json
import os
import resource
import time

#: EWMA smoothing for service times (the reference keeps a plain running
#: average; we record both)
ALPHA = 0.1


def node_stats_name(dataflow_name: str, idx: int, node_name: str) -> str:
    """Canonical per-node id: the NodeStats name, the ``<trace_dir>/*.log``
    filename stem, and the ``id`` field of every metrics.jsonl node entry
    — one definition so the three can never drift apart."""
    return f"{dataflow_name}_{idx:02d}_{node_name}"


#: how many of a node's waits (and of a phase's spans) share one that the
#: CPU clock follows; ``cpu_every()`` sets it on first use
_CPU_EVERY = None


def cpu_every() -> int:
    """The stride of the CPU clock.  A read is a system call made holding
    the interpreter lock: 0.3-0.5 us on a plain Linux kernel, where every
    wait and span is followed (1); 6 us alone and far more beside ten busy
    threads where a sandbox's kernel answers it (the benchmark's chip host:
    a read at every boundary cost ``pipe_cb.paced`` half its latency,
    PERF.md PR 36), where every 17th is (odd: a node that serves two inputs
    in turn follows both) and the totals are scaled up from those."""
    global _CPU_EVERY
    if _CPU_EVERY is None:
        cost = None
        for _ in range(5):  # the cheapest of five: being preempted is no cost
            t0 = time.perf_counter_ns()
            for _ in range(8):
                time.thread_time_ns()
            dt = (time.perf_counter_ns() - t0) / 8
            cost = dt if cost is None else min(cost, dt)
        _CPU_EVERY = 1 if cost < 2000 else 17
    return _CPU_EVERY


def _switches():
    """The calling thread's context switches so far, (voluntary: it waited
    for a lock or slept; involuntary: it was pushed off its core)."""
    ru = resource.getrusage(resource.RUSAGE_THREAD)
    return ru.ru_nvcsw, ru.ru_nivcsw


def _cpu_of(wall_ns, followed) -> float:
    """The CPU inside ``wall_ns`` of a node's time, from the part of it
    the CPU clock followed, ``[wall ns, CPU ns]``: all of it (then this is
    the CPU read), or every ``cpu_every()``-th piece scaled up."""
    wall, cpu = followed
    return cpu * wall_ns / wall if wall else 0.0


class NodeStats:
    """Counter block attached to a node when tracing is enabled.  Built on
    the node's own thread as it starts (the engine does): the switch counts
    ``write`` reports are that thread's."""

    __slots__ = ("name", "rcv_batches", "rcv_tuples", "svc_time_ns_total",
                 "avg_ts_us", "ewma_ts_us", "idle_ns", "blocked_ns",
                 "blocked_max_ns", "blocked_max_inbox", "fused_svc_ns",
                 "_fused_open", "counters", "started_ns", "cpu_on",
                 "_cpu_left", "_run_cpu0", "run_cpu_ns", "idle_cpu",
                 "blocked_cpu", "fused_cpu", "_switches0")

    def __init__(self, name: str):
        self.name = name
        self.rcv_batches = 0
        self.rcv_tuples = 0
        self.svc_time_ns_total = 0   # inclusive: blocked puts are inside
        self.avg_ts_us = 0.0      # running mean service time per batch
        self.ewma_ts_us = 0.0     # EWMA service time per batch
        self.idle_ns = 0          # waiting in inbox.get()
        self.blocked_ns = 0       # inside inbox.put() from emit/emit_to
        self.blocked_max_ns = 0   # the longest single put ...
        self.blocked_max_inbox = None   # ... and whose inbox it was on
        #: a fused chain's service time per stage, each stage's own: the
        #: stages after it and the chain's blocked puts are taken out
        self.fused_svc_ns = {}
        self._fused_open = []     # per open fused svc: [ns, CPU ns] below it
        self.counters = {}        # node-specific extras (windows_fired, ...)
        self.started_ns = time.perf_counter_ns()
        #: the thread's CPU clock.  The node's whole run (``generate()``
        #: or its receive loop) is read once, by the engine, as it ends;
        #: what of it is not the node's own work is read piece by piece: a
        #: get, a put and what a fused put holds, [wall ns, CPU ns] of the
        #: pieces the clock followed.  ``cpu_turn`` decides as a get or an
        #: outermost put opens, ``cpu_on`` holds it for what is nested
        self._run_cpu0 = 0
        self.run_cpu_ns = None
        self.cpu_on = False
        self._cpu_left = 1        # the first piece is followed
        self.idle_cpu = [0, 0]
        self.blocked_cpu = [0, 0]
        self.fused_cpu = {}
        self._switches0 = _switches()

    # -- recording (hot path: branch-free beyond attribute math) -----------

    def run_begins(self):
        self._run_cpu0 = time.thread_time_ns()

    def run_ends(self):
        self.run_cpu_ns = time.thread_time_ns() - self._run_cpu0

    def cpu_turn(self):
        """A get or an outermost put opens: the CPU clock follows every
        ``cpu_every()``-th."""
        left = self._cpu_left - 1
        if left:
            self._cpu_left = left
            self.cpu_on = False
        else:
            self._cpu_left = cpu_every()
            self.cpu_on = True

    def clocks(self):
        """``(wall_ns, cpu_ns)`` at a boundary of the open piece; the CPU
        clock only if it follows this one (else 0)."""
        return (time.perf_counter_ns(),
                time.thread_time_ns() if self.cpu_on else 0)

    def _followed(self, pair, dt_ns: int, cpu_ns: int):
        if self.cpu_on:
            pair[0] += dt_ns
            pair[1] += cpu_ns

    def record_idle(self, dt_ns: int, cpu_ns: int):
        self.idle_ns += dt_ns
        self._followed(self.idle_cpu, dt_ns, cpu_ns)

    def record_svc(self, n_rows: int, dt_ns: int):
        self.rcv_batches += 1
        self.rcv_tuples += n_rows
        self.svc_time_ns_total += dt_ns
        us = dt_ns / 1e3
        n = self.rcv_batches
        self.avg_ts_us += (us - self.avg_ts_us) / n
        self.ewma_ts_us = (us if n == 1
                           else self.ewma_ts_us + ALPHA * (us - self.ewma_ts_us))

    def timed_put(self, inbox, src: int, batch):
        """``inbox.put`` on the node's clocks.  An inter-thread inbox: the
        time is blocked time.  A fused edge (runtime/comb.py ``_SyncOut``):
        the put IS the next stage's svc, booked to that stage."""
        fused = getattr(inbox, "dst", None)
        if not self._fused_open:
            self.cpu_turn()
        if fused is not None:
            self._fused_open.append([0, 0])
        t0, c0 = self.clocks()
        inbox.put(src, batch)
        t1, c1 = self.clocks()
        dt, cpu = t1 - t0, c1 - c0
        if fused is not None:
            below, below_cpu = self._fused_open.pop()
            name = fused.name
            self.fused_svc_ns[name] = (
                self.fused_svc_ns.get(name, 0) + dt - below)
            self._followed(self.fused_cpu.setdefault(name, [0, 0]),
                           dt - below, cpu - below_cpu)
        else:
            self._blocked(dt, cpu, getattr(inbox, "owner", None))
        if self._fused_open:
            above = self._fused_open[-1]
            above[0] += dt
            above[1] += cpu

    def _blocked(self, dt, cpu, on):
        self.blocked_ns += dt
        self._followed(self.blocked_cpu, dt, cpu)
        if dt > self.blocked_max_ns:
            self.blocked_max_ns = dt
            self.blocked_max_inbox = on

    def timed_wait(self, event, timeout):
        """``event.wait`` on the node's clocks: blocked time, as a put into
        a full inbox is (a farm emitter that waits out a worker's turn,
        patterns/win_farm.py)."""
        if not self._fused_open:
            self.cpu_turn()
        t0, c0 = self.clocks()
        event.wait(timeout)
        t1, c1 = self.clocks()
        dt, cpu = t1 - t0, c1 - c0
        self._blocked(dt, cpu, "a worker's turn")
        if self._fused_open:
            above = self._fused_open[-1]
            above[0] += dt
            above[1] += cpu

    def bump(self, counter: str, n: int = 1):
        self.counters[counter] = self.counters.get(counter, 0) + n

    def record_shed(self, n: int = 1):
        """Items dropped from this node's inbox by a shedding
        OverloadPolicy (runtime/overload.py) — folded in once at node
        end by the engine, so the hot path stays counter-free."""
        self.bump("shed", n)

    def record_quarantined(self, n: int = 1):
        """Poison batches parked in the dead-letter queue instead of
        tearing the graph down (error-budget quarantine)."""
        self.bump("quarantined", n)

    # -- reporting ----------------------------------------------------------

    def snapshot(self) -> dict:
        alive_s = (time.perf_counter_ns() - self.started_ns) / 1e9
        idle_cpu = _cpu_of(self.idle_ns, self.idle_cpu)
        blocked_cpu = _cpu_of(self.blocked_ns, self.blocked_cpu)
        # the run's CPU that is not a wait's is the node's own work's (and
        # its loop's between two calls); known once the run has ended
        self_cpu = ({} if self.run_cpu_ns is None else {
            "self_cpu_ms_total": round(
                (self.run_cpu_ns - idle_cpu - blocked_cpu) / 1e6, 3)})
        return {
            "node": self.name,
            "rcv_batches": self.rcv_batches,
            "rcv_tuples": self.rcv_tuples,
            "svc_time_ms_total": round(self.svc_time_ns_total / 1e6, 3),
            "self_ms_total": round(
                (self.svc_time_ns_total - self.blocked_ns) / 1e6, 3),
            # self minus this is the node's off-CPU time: in service and
            # not running (the interpreter lock, a lock, a sleep, its core)
            **self_cpu,
            "blocked_ms_total": round(self.blocked_ns / 1e6, 3),
            "idle_ms_total": round(self.idle_ns / 1e6, 3),
            # CPU burnt inside get() and blocked put(): a wait should burn
            # next to none
            "wait_cpu_ms_total": round((idle_cpu + blocked_cpu) / 1e6, 3),
            "blocked_max_ms": round(self.blocked_max_ns / 1e6, 3),
            "blocked_max_inbox": self.blocked_max_inbox,
            "fused_svc_ms": {k: round(v / 1e6, 3)
                             for k, v in self.fused_svc_ns.items()},
            "fused_cpu_ms": {
                k: round(_cpu_of(v, self.fused_cpu.get(k, (0, 0))) / 1e6, 3)
                for k, v in self.fused_svc_ns.items()},
            "avg_service_us_per_batch": round(self.avg_ts_us, 3),
            "ewma_service_us_per_batch": round(self.ewma_ts_us, 3),
            "alive_sec": round(alive_s, 3),
            **self.counters,
        }

    def write(self, trace_dir: str):
        """The node's ``.log``: the snapshot, and the context switches of
        the calling thread since the stats were built -- the node's own
        thread as it ends."""
        voluntary, involuntary = _switches()
        log = self.snapshot()
        log["ctx_voluntary"] = voluntary - self._switches0[0]
        log["ctx_involuntary"] = involuntary - self._switches0[1]
        os.makedirs(trace_dir, exist_ok=True)
        safe = self.name.replace("/", "_")
        path = os.path.join(trace_dir, f"{safe}.log")
        with open(path, "w") as f:
            json.dump(log, f, indent=1)
            f.write("\n")


def default_trace_dir() -> str | None:
    """The WF_LOG_DIR environment hook (the -DLOG_DIR analog)."""
    return os.environ.get("WF_LOG_DIR") or None


def default_sample_period() -> float | None:
    """The WF_SAMPLE_PERIOD environment hook: seconds between live
    metrics samples (obs/sampler.py).  Lets any existing program — the
    benchmarks, scripts/soak_overload.py — opt into in-flight telemetry
    with no code change, exactly like WF_LOG_DIR enables end-of-run
    tracing.  Unset/empty = no sampler thread (docs/OBSERVABILITY.md)."""
    raw = os.environ.get("WF_SAMPLE_PERIOD")
    if not raw:
        return None
    period = float(raw)
    if period <= 0:
        raise ValueError(
            f"WF_SAMPLE_PERIOD must be positive seconds, got {raw!r}")
    return period
