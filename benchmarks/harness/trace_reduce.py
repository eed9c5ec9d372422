"""From a profiler trace (``.xplane.pb``) to the numbers the benchmark reports.

Read with ``jax.profiler.ProfileData`` and nothing else.  What is taken:

* device planes are those named ``/device:TPU:<n>``; on each, the line
  ``XLA Ops`` holds one event per operation that ran on the chip and the line
  ``XLA Modules`` one event per executable launched;
* ``busy_s`` of a chip is the union of its ``XLA Ops`` intervals (its modules'
  where the line is missing), ``window_s`` the span of the whole trace (first
  start to last end over every plane, host threads included), and the idle
  share ``1 - busy_s / window_s``; several chips are averaged;
* per-executable device time is the sum of the durations on ``XLA Modules``,
  by the executable's name as the trace gives it;
* idle gaps are the stretches between a chip's busy intervals, the longest
  first, each named by what the host was doing in it: the program's own
  phase (a host annotation whose name starts with ``wf.``) that covers most
  of it where one overlaps it, else the benchmark's own annotation (names
  that start with ``bench.``) that covers most of it, else ``unattributed``.
  The generator is inside its push for nearly the whole of a closed loop, so
  its annotation says nothing where the program says something.

``benchmarks/tests/test_trace_reduce.py`` checks all of it on the small traces
recorded beside this file.
"""

from __future__ import annotations

import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
#: host annotations that name an idle gap, the preferred kind first
ANNOTATION_PREFIXES = ("wf.", "bench.")


def find_xplane(trace_dir):
    """The newest ``.xplane.pb`` under a ``start_trace`` directory."""
    paths = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load(path):
    """``{plane name: {line name: [(event name, start_ns, end_ns), ...]}}``.
    Lines of one name (threads that share a name) are merged."""
    from jax.profiler import ProfileData
    planes = {}
    for plane in ProfileData.from_file(path).planes:
        lines = planes.setdefault(plane.name, {})
        for line in plane.lines:
            events = lines.setdefault(line.name, [])
            for ev in line.events:
                start = float(ev.start_ns)
                events.append((ev.name, start, start + float(ev.duration_ns)))
    return planes


def union(intervals):
    """Sorted, merged ``[(start, end)]`` of possibly overlapping intervals."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1] = (merged[-1][0], end)
        else:
            merged.append((start, end))
    return merged


def _overlap(a_start, a_end, b_start, b_end):
    return max(0.0, min(a_end, b_end) - max(a_start, b_start))


def reduce_planes(planes, top=10):
    """The numbers, from what :func:`load` returns.  Times in seconds."""
    starts, ends = [], []
    annotations = []
    for pname, lines in planes.items():
        for lname, events in lines.items():
            for name, start, end in events:
                starts.append(start)
                ends.append(end)
                if name.startswith(ANNOTATION_PREFIXES) \
                        and not DEVICE_PLANE.match(pname):
                    annotations.append((name, start, end))
    if not starts:
        raise ValueError("the trace holds no event")
    t_lo, t_hi = min(starts), max(ends)
    devices = sorted(p for p in planes if DEVICE_PLANE.match(p))
    busy, per_exec, per_op, gaps = [], {}, {}, []
    for pname in devices:
        lines = planes[pname]
        ops = lines.get(OPS_LINE) or lines.get(MODULES_LINE) or []
        merged = union((s, e) for _, s, e in ops)
        busy.append(sum(e - s for s, e in merged))
        for name, s, e in lines.get(MODULES_LINE, []):
            sec, n = per_exec.get(name, (0.0, 0))
            per_exec[name] = (sec + (e - s) / 1e9, n + 1)
        for name, s, e in lines.get(OPS_LINE, []):
            # the trace names an operation by its whole HLO line; the
            # instruction's own name is what stands before the " = "
            name = name.split(" = ", 1)[0]
            per_op[name] = per_op.get(name, 0.0) + (e - s) / 1e9
        edges = [(t_lo, t_lo)] + merged + [(t_hi, t_hi)]
        for (_, prev_end), (next_start, _) in zip(edges, edges[1:]):
            if next_start > prev_end:
                gaps.append((prev_end, next_start))
    gaps.sort(key=lambda g: g[0] - g[1])
    idle_gaps = []
    for g_start, g_end in gaps[:top]:
        by_name = {}
        for name, a_start, a_end in annotations:
            ov = _overlap(g_start, g_end, a_start, a_end)
            if ov > 0:
                by_name[name] = by_name.get(name, 0.0) + ov
        best = "unattributed"
        for prefix in ANNOTATION_PREFIXES:
            of_kind = {n: ov for n, ov in by_name.items()
                       if n.startswith(prefix)}
            if of_kind:
                best = max(of_kind, key=of_kind.get)
                break
        idle_gaps.append([best, (g_end - g_start) / 1e9])
    n_dev = len(devices)
    return {
        "n_devices": n_dev,
        "window_s": (t_hi - t_lo) / 1e9,
        "busy_s": (sum(busy) / n_dev / 1e9) if n_dev else 0.0,
        "executables": per_exec,               # name -> (seconds, launches)
        "device_ops": [[n, s] for n, s in sorted(
            per_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": idle_gaps,
    }


def reduce_file(path, top=10):
    return reduce_planes(load(path), top=top)
