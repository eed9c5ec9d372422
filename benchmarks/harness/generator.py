"""The benchmark's load generator: one general generator, driven by a mix file.

It knows nothing of any deployment.  A configuration's plain reference
(``configs/<name>_oracle.py``) defines the stream as a function of
``(seed, event index)``; this module turns that into template chunks of the
record dtype the configuration hands it (before the window), and in the
window copies, stamps and pushes them from the callable it gives to the
program's ``Source``.  The program sees only the generated batches.

Two loops, chosen by the mix file's ``loop``:

``closed``  full speed: the next chunk is built as soon as the last push
            returned, so only backpressure slows it.  Every event of a chunk
            carries the generator's clock at the chunk's creation.
``open``    event *i* is due at ``i / rate`` seconds and carries that due time
            as its event time.  A chunk is pushed when its last event is due,
            never earlier, and the schedule does not slow when the system
            does: lateness (actual push - due) is logged per chunk.  A
            chunk that is late is pushed all the same, after the window's
            close too (``handed_over`` counts those pushed before it,
            ``pushed`` all of them); only a generator more than
            ``GIVE_UP_S`` behind when the stream should have ended stops.

A stream may carry its own event times: where the reference module's
``columns()`` yields a ``ts`` column, it is each event's offset (microseconds,
any sign) from its chunk's base -- the generator's clock at the chunk's
creation in the closed loop, the due time of the chunk's first event in the
open loop -- and the generator adds the base to it instead of stamping its
own.  Late and out-of-order events are such a stream's to define; the
schedule (when a chunk is pushed) is the same either way.

Imports numpy and the standard library only.
"""

from __future__ import annotations

import contextlib
import math
import sys
import time

import numpy as np

_NULL = contextlib.nullcontext()


def build_templates(stream, cfg, seed, dtype, chunk):
    """Template chunks covering one whole number of stream periods.

    ``stream`` is the reference module: ``stream.columns(cfg, seed, start, n)``
    gives the columns of events ``start .. start+n-1`` and
    ``stream.period_events(cfg)`` the number of events after which the stream
    repeats (ids apart).  The set holds ``lcm(chunk, period) / chunk`` chunks.
    Returns ``(templates, id_shift, own_ts)``; ``own_ts`` says that the stream
    carries its own event times (a ``ts`` column, held in the templates as
    offsets from the chunk's base).
    """
    period = stream.period_events(cfg)
    set_events = math.lcm(chunk, period)
    n_templates = set_events // chunk
    if n_templates * chunk * dtype.itemsize > 2 << 30:
        raise ValueError(
            f"template set of {n_templates} x {chunk} events needs over 2 GiB")
    templates, own_ts = [], False
    for j in range(n_templates):
        cols = stream.columns(cfg, seed, j * chunk, chunk)
        own_ts = own_ts or "ts" in cols
        t = np.zeros(chunk, dtype=dtype)
        for name, col in cols.items():
            t[name] = col
        templates.append(t)
    return templates, stream.id_shift(cfg, set_events), own_ts


def due_offsets_us(chunk, rate):
    """Due time of the chunk's k-th event after the chunk's first, in whole
    microseconds (open loop); zeros when there is no rate (closed loop)."""
    if not rate:
        return np.zeros(chunk, dtype=np.int64)
    return (np.arange(chunk, dtype=np.int64) * 1_000_000) // int(rate)


def chunk_base_us(j, chunk, rate):
    """Due time of chunk ``j``'s first event, whole microseconds (open loop)."""
    return (j * chunk * 1_000_000) // int(rate)


class ChunkLog:
    """What was pushed: the oracle's inputs, and the generator's own times.

    The stream runs on for the mix's ``tail_seconds`` after the window closes
    (a stream processor's input does not end when a measurement does), so the
    log holds the window's chunks first and the tail's after them."""

    def __init__(self, chunk, off_us, ts_max_us=None):
        self.chunk = chunk
        self.off_us = off_us          # per-event due offset inside a chunk
        # a stream with its own event times: the latest offset from the
        # chunk's base in each template chunk (None: the generator stamps)
        self.ts_max_us = ts_max_us
        self.base_us = []             # event time of each chunk's first event
        self.late_us = []             # open loop: actual push - due
        self.busy_ns = 0              # building + stamping, window only
        self.blocked_ns = 0           # inside push_batch, window only
        self.t0_ns = None             # window start, monotonic
        self.t_window_end_ns = None   # the window's last push returned
        self.window_chunks = 0        # chunks that belong to the window
        self.handed_over = 0          # ... and were pushed before it closed
        self.pushed = 0               # ... and were pushed at all, late or not

    @property
    def n_chunks(self):
        return len(self.base_us)

    @property
    def own_ts(self):
        """Whether the stream carries its own event times."""
        return self.ts_max_us is not None

    def window_last_event_us(self):
        """The latest event time of the window's events."""
        n = min(self.window_chunks, len(self.base_us))
        if not n:
            return -1
        if not self.own_ts:
            return int(self.base_us[n - 1]) + int(self.off_us[-1])
        base = np.asarray(self.base_us[:n], dtype=np.int64)
        return int((base + self.ts_max_us[np.arange(n)
                                          % len(self.ts_max_us)]).max())

    def for_oracle(self):
        """What the reference needs to recompute every event's time: chunk
        ``j`` has the base ``base_us[j]``; its ``k``-th event is due at
        ``base_us[j] + off_us[k]``, which is also its event time unless the
        stream carries its own (``own_ts``): then that is the base plus the
        ``ts`` the reference's own ``columns()`` gives the event."""
        return {"chunk": self.chunk,
                "base_us": np.asarray(self.base_us, dtype=np.int64),
                "off_us": self.off_us,
                "own_ts": self.own_ts}


class Generator:
    """Callable for the program's ``Source``: ``gen(shipper)``.

    ``mix`` is the parsed traffic file; ``chunk`` and ``rate`` come from the
    cell; ``seconds`` is the window and ``tail_seconds`` how long the stream
    runs on after it.  ``own_ts`` says that the templates' ``ts`` column holds
    the stream's own event times as offsets from the chunk's base.
    ``clock_ns`` and ``sleep`` can be replaced in tests.  ``annotate`` wraps
    the push in a profiler annotation in traced runs.
    """

    #: an open loop that has fallen this far behind its schedule stops
    GIVE_UP_S = 10.0
    #: chunk buffers kept for reuse
    POOL_MAX = 32

    def __init__(self, templates, id_shift, mix, chunk, rate, seconds,
                 tail_seconds=None, clock_ns=time.monotonic_ns,
                 sleep=time.sleep, annotate=None, own_ts=False):
        self.templates = templates
        self.id_shift = int(id_shift)
        self.loop = mix["loop"]
        if self.loop not in ("closed", "open"):
            raise ValueError(f"mix loop {self.loop!r} is neither closed nor open")
        if self.loop == "open" and not rate:
            raise ValueError("an open-loop mix needs the cell's rate")
        self.rate = rate if self.loop == "open" else None
        self.chunk = chunk
        self.seconds = seconds
        self.tail_seconds = float(mix.get("tail_seconds", 0.0)
                                  if tail_seconds is None else tail_seconds)
        self.clock_ns = clock_ns
        self.sleep = sleep
        self.annotate = annotate
        self.log = ChunkLog(
            chunk, due_offsets_us(chunk, self.rate),
            np.asarray([int(t["ts"].max()) for t in templates],
                       dtype=np.int64) if own_ts else None)
        self._pool = []
        self.on_start = None          # called with t0_ns as the window opens
        self.on_window_end = None     # called as the window's last push returned

    # -- one chunk ---------------------------------------------------------
    def _buffer(self, like):
        """A chunk-sized array nobody else holds any more, or a new one.

        A pushed batch belongs to the program (it may change it in place and
        keep it as long as it likes), so a buffer is taken again only when the
        pool's own reference is the last one: views and slices the program
        keeps hold a reference to their base.  Reuse saves the page faults of
        a fresh 35 MB allocation per chunk, which would otherwise be most of
        the generator's time."""
        for buf in self._pool:
            if sys.getrefcount(buf) == 3:     # the pool, ``buf``, the call
                return buf
        buf = np.empty_like(like)
        if len(self._pool) < self.POOL_MAX:
            self._pool.append(buf)
        return buf

    def _make(self, j, base_us):
        n_t = len(self.templates)
        t = self.templates[j % n_t]
        b = self._buffer(t)
        # as bytes: numpy copies a structured array field by field otherwise
        np.copyto(b.view(np.uint8), t.view(np.uint8))
        cycle = j // n_t
        if cycle and self.id_shift:
            b["id"] += cycle * self.id_shift
        if self.log.own_ts:
            np.add(t["ts"], base_us, out=b["ts"])
        elif self.rate:
            np.add(self.log.off_us, base_us, out=b["ts"])
        else:
            b["ts"] = base_us
        return b

    def _push(self, shipper, b):
        with (self.annotate("bench.gen_blocked_in_push")
              if self.annotate else _NULL):
            shipper.push_batch(b)

    # -- the loops -----------------------------------------------------------
    def __call__(self, shipper):
        log = self.log
        log.t0_ns = t0 = self.clock_ns()
        if self.on_start is not None:
            self.on_start(t0)
        end_ns = t0 + int(self.seconds * 1e9)
        stop_ns = end_ns + int(self.tail_seconds * 1e9)
        if self.loop == "closed":
            self._closed(shipper, t0, end_ns, stop_ns)
        else:
            self._open(shipper, t0, end_ns)

    def _closed(self, shipper, t0, end_ns, stop_ns):
        log = self.log
        j = 0
        while True:
            now = self.clock_ns()
            if now >= end_ns and log.t_window_end_ns is None:
                log.t_window_end_ns = now
                log.window_chunks = log.handed_over = log.pushed = j
                if self.on_window_end is not None:
                    self.on_window_end()
            if now >= stop_ns:
                break
            in_window = log.t_window_end_ns is None
            base_us = (now - t0) // 1000
            b = self._make(j, base_us)
            log.base_us.append(base_us)
            t_built = self.clock_ns()
            self._push(shipper, b)
            if in_window:
                log.busy_ns += t_built - now
                log.blocked_ns += self.clock_ns() - t_built
            j += 1

    def _open(self, shipper, t0, end_ns):
        log = self.log
        period_ns = self.chunk * 1e9 / self.rate
        # the window's schedule keeps one chunk period of grace before the
        # close, so that a generator that is on time hands over its last
        n_window = max(int(self.seconds * self.rate // self.chunk) - 1, 1)
        n_tail = int(math.ceil(self.tail_seconds * self.rate / self.chunk))
        log.window_chunks = n_window
        give_up_ns = end_ns + int((self.tail_seconds + self.GIVE_UP_S) * 1e9)
        for j in range(n_window + n_tail):
            t = self.clock_ns()
            base_us = chunk_base_us(j, self.chunk, self.rate)
            b = self._make(j, base_us)
            t_built = self.clock_ns()
            due_ns = t0 + int((j + 1) * period_ns)
            while True:
                now = self.clock_ns()
                if now >= due_ns:
                    break
                self.sleep((due_ns - now) / 1e9)
            if now >= give_up_ns:
                break
            log.base_us.append(base_us)
            log.late_us.append((now - due_ns) / 1e3)
            self._push(shipper, b)
            if j < n_window:
                log.pushed += 1
                log.busy_ns += t_built - t
                t_pushed = self.clock_ns()
                log.blocked_ns += t_pushed - now
                if now < end_ns:
                    log.handed_over += 1
                if j == n_window - 1:
                    log.t_window_end_ns = max(t_pushed, end_ns)
                    if self.on_window_end is not None:
                        self.on_window_end()
        if log.t_window_end_ns is None:
            log.t_window_end_ns = self.clock_ns()
            if self.on_window_end is not None:
                self.on_window_end()
