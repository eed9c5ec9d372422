"""Who the chip was waiting for: the device's idle time in a traced slice,
split by what the program's ship threads were doing.

The program writes its ship phases into the profiler's own trace as host
annotations named ``wf.<phase>`` with the stats ``launch`` and ``shard``
(``shard`` numbers a ship thread).  From one ``.xplane.pb``, read with
``jax.profiler.ProfileData`` and nothing else:

* the device's idle stretches are the complement of the union of its
  ``XLA Ops`` intervals over the whole trace, as ``trace_reduce`` takes them;
* the host's clock and the device's differ: the offset is estimated as the
  median of (start of a step executable on ``XLA Modules``) - (start of the
  nearest ``wf.dispatch``), and an idle stretch shorter than twice its size
  is left unattributed, since either side of it may belong to its neighbour;
* a ship thread is *idle* inside its ``wf.ship_idle`` events, and before its
  first and after its last event of the trace (an annotation open when the
  trace starts or stops is not recorded, and the ship phases are short while
  the idle waits are long);
* an instant of an idle stretch is **starved** when every ship thread is
  idle; otherwise it goes to the ship phase open on some ship thread, the
  one nearest the device first (``PHASES``), or to ``between phases`` when a
  ship thread is out of ``wf.ship_idle`` but inside no named phase;
* the starved time is split once more by whether some thread was inside
  ``wf.native_bookkeeping`` (a window worker feeding its core) or none was
  (the workers were waiting for input themselves).

``benchmarks/tests/test_idle_attribution.py`` checks it against a brute-force
timeline on the small trace recorded beside this file.
"""

from __future__ import annotations

import statistics

from harness.trace_reduce import (DEVICE_PLANE, MODULES_LINE, OPS_LINE,
                                  union)

PREFIX = "wf."
IDLE = "ship_idle"
#: ship phases, the one nearest the device first
PHASES = ("dispatch", "device_put", "launch_take", "launch_coalesce",
          "harvest_wait")
FEEDING = "native_bookkeeping"
STEP_PREFIX = "jit_wf_step"


def load(path):
    """What the attribution needs of one trace: ``{"span": (lo, hi),
    "ops": [(start, end)], "modules": [(name, start, end)], "wf": [(phase,
    start, end, launch, shard)]}``, times in ns; chips' ops are pooled."""
    from jax.profiler import ProfileData
    lo, hi = float("inf"), float("-inf")
    ops, modules, wf = [], [], []
    for plane in ProfileData.from_file(path).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            for ev in line.events:
                start = float(ev.start_ns)
                end = start + float(ev.duration_ns)
                lo, hi = min(lo, start), max(hi, end)
                if device:
                    if line.name == OPS_LINE:
                        ops.append((start, end))
                    elif line.name == MODULES_LINE:
                        modules.append((ev.name, start, end))
                elif ev.name.startswith(PREFIX):
                    stats = dict(ev.stats)
                    wf.append((ev.name[len(PREFIX):], start, end,
                               stats.get("launch"), stats.get("shard")))
    return {"span": (lo, hi), "ops": ops, "modules": modules, "wf": wf}


def total(intervals):
    return sum(e - s for s, e in intervals)


def intersect(a, b):
    """Intersection of two sorted lists of disjoint intervals."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if s < e:
            out.append((s, e))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def subtract(a, b):
    """``a`` without ``b``, both sorted lists of disjoint intervals."""
    out, j = [], 0
    for s, e in a:
        while j < len(b) and b[j][1] <= s:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > s:
                out.append((s, b[k][0]))
            s = max(s, b[k][1])
            k += 1
        if s < e:
            out.append((s, e))
    return out


def clock_offset_ns(trace):
    """Median of (a step executable's start on the device) - (the start of
    the ``wf.dispatch`` nearest to it on the host); None without both."""
    starts = sorted(s for phase, s, _e, _l, _sh in trace["wf"]
                    if phase == "dispatch")
    steps = [s for name, s, _e in trace["modules"]
             if name.startswith(STEP_PREFIX)]
    if not starts or not steps:
        return None
    return statistics.median(
        m - min(starts, key=lambda s, m=m: abs(m - s)) for m in steps)


def attribute(trace):
    """Idle seconds of the device by what the ship threads were doing.
    None when the trace holds no ``wf.`` event of a ship thread or no
    device operation."""
    lo, hi = trace["span"]
    busy = union(trace["ops"])
    shards = sorted({sh for _p, _s, _e, _l, sh in trace["wf"]
                     if sh is not None})
    if not busy or not shards:
        return None
    offset = clock_offset_ns(trace)
    if offset is None:
        return None
    idle = subtract([(lo, hi)], busy)
    floor = 2.0 * abs(offset)
    gaps = [(s, e) for s, e in idle if e - s >= floor]

    starved = [(lo, hi)]
    for sh in shards:
        mine = [(s, e) for p, s, e, _l, x in trace["wf"] if x == sh]
        first = min(s for s, _e in mine)
        last = max(e for _s, e in mine)
        resting = union([(s, e) for p, s, e, _l, x in trace["wf"]
                         if x == sh and p == IDLE]
                        + [(lo, first), (last, hi)])
        starved = intersect(starved, resting)

    seconds = {}
    left = gaps
    took = intersect(left, starved)
    seconds[IDLE] = total(took) / 1e9
    left = subtract(left, starved)
    for phase in PHASES:
        held = union((s, e) for p, s, e, _l, _x in trace["wf"] if p == phase)
        seconds[phase] = total(intersect(left, held)) / 1e9
        left = subtract(left, held)
    feeding = union((s, e) for p, s, e, _l, _x in trace["wf"]
                    if p == FEEDING)
    return {
        "idle_s": total(idle) / 1e9,
        "attributable_s": total(gaps) / 1e9,
        "by_phase_s": seconds,
        "between_phases_s": total(left) / 1e9,
        "starved_while_feeding_s": total(intersect(took, feeding)) / 1e9,
        "clock_offset_ms": offset / 1e6,
        "ship_threads": len(shards),
    }
