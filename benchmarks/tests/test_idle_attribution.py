"""The idle attribution: its interval arithmetic and its rules on a timeline
made by hand, then against a brute-force reading of the second small trace,
recorded on a TPU v5 lite by ``record_wf_trace.py`` (two threads playing ship
threads 0 and 1 through the program's own ``profile.span``, three launches
each of the program's regular step, a main thread in ``native_bookkeeping``).
"""

import os

import pytest

from conftest import BENCH
from harness import idle_attribution as ia
from harness import trace_reduce

TRACE = os.path.join(BENCH, "harness", "testdata", "wf_trace.xplane.pb")


def test_intersect_and_subtract():
    a = [(0, 10), (20, 30), (40, 50)]
    b = [(5, 25), (28, 45), (60, 70)]
    assert ia.intersect(a, b) == [(5, 10), (20, 25), (28, 30), (40, 45)]
    assert ia.subtract(a, b) == [(0, 5), (25, 28), (45, 50)]
    assert ia.subtract(a, []) == a and ia.intersect(a, []) == []
    assert ia.subtract([(0, 10)], [(0, 10)]) == []
    assert ia.subtract([(0, 10)], [(2, 3), (5, 6)]) == [(0, 2), (3, 5),
                                                        (6, 10)]
    assert ia.total(a) == 30


def made_up():
    """ms on a timeline of 100; the device works 10-12, 50-53 and 90-91.
    Ship thread 0 idles until 8, takes 8-9, puts 9-9.5, dispatches 9.5-10,
    then is out of every phase 10-14, idles 14-100.  Ship thread 1's first
    event is an idle 20-48, then put 48-49.4, dispatch 49.4-50, harvest
    50-60 (its idle after that is cut by the end of the trace).  A worker
    feeds its core 30-40."""
    ms = 1e6
    wf = [("ship_idle", 0, 8, None, 0), ("launch_take", 8, 9, 1, 0),
          ("device_put", 9, 9.5, 1, 0), ("dispatch", 9.5, 10, 1, 0),
          ("ship_idle", 14, 100, None, 0),
          ("ship_idle", 20, 48, None, 1), ("device_put", 48, 49.4, 2, 1),
          ("dispatch", 49.4, 50, 2, 1), ("harvest_wait", 50, 60, 2, 1),
          ("native_bookkeeping", 30, 40, None, None)]
    return {"span": (0.0, 100 * ms),
            "ops": [(10 * ms, 12 * ms), (50 * ms, 53 * ms),
                    (90 * ms, 91 * ms)],
            "modules": [("jit_wf_step_regular(1)", 10 * ms, 12 * ms),
                        ("jit_wf_step_regular(1)", 50 * ms, 53 * ms),
                        ("jit_other(2)", 90 * ms, 91 * ms)],
            "wf": [(p, s * ms, e * ms, l, sh) for p, s, e, l, sh in wf]}


def test_rules_on_a_timeline_made_by_hand():
    got = ia.attribute(made_up())
    # offsets: 10 - 9.5 and 50 - 49.4, median 0.55 ms; no gap is under 1.1
    assert got["clock_offset_ms"] == pytest.approx(0.55)
    assert got["ship_threads"] == 2
    assert got["idle_s"] == pytest.approx(0.094)
    assert got["attributable_s"] == pytest.approx(0.094)
    by = got["by_phase_s"]
    # idle stretches: 0-10, 12-50, 53-90, 91-100.  Thread 1 counts as idle
    # before its first event (20) and after its last (60).
    # starved: 0-8, 14-48, 60-90, 91-100 -> 8 + 34 + 30 + 9 = 81
    assert by["ship_idle"] == pytest.approx(0.081)
    assert by["dispatch"] == pytest.approx(0.0005 + 0.0006)
    assert by["device_put"] == pytest.approx(0.0005 + 0.0014)
    assert by["launch_take"] == pytest.approx(0.001)
    assert by["harvest_wait"] == pytest.approx(0.007)       # 53-60
    assert got["between_phases_s"] == pytest.approx(0.002)  # 12-14
    assert got["starved_while_feeding_s"] == pytest.approx(0.010)
    assert sum(by.values()) + got["between_phases_s"] \
        == pytest.approx(got["attributable_s"])


def test_short_stretches_stay_unattributed_and_no_ship_thread_is_no_answer():
    t = made_up()
    # a 0.4 ms hole in the first op: under twice the 0.55 ms offset
    t["ops"] = [(10e6, 10.8e6), (11.2e6, 12e6)] + t["ops"][1:]
    got = ia.attribute(t)
    assert got["idle_s"] - got["attributable_s"] == pytest.approx(0.0004)
    t["wf"] = [w for w in t["wf"] if w[4] is None]
    assert ia.attribute(t) is None
    t = made_up()
    t["modules"] = [m for m in t["modules"] if "wf_step" not in m[0]]
    assert ia.attribute(t) is None          # a parent's trace: jit_step only


# -- the recorded trace -------------------------------------------------------

@pytest.fixture(scope="module")
def trace():
    return ia.load(TRACE)


def test_recorded_trace_holds_the_programs_annotations(trace):
    by_launch = {}
    for phase, s, e, launch, shard in trace["wf"]:
        assert e >= s
        if launch is not None:
            by_launch.setdefault(launch, {})[phase] = (s, e, shard)
    assert len(by_launch) == 6
    for phases in by_launch.values():
        assert set(phases) == {"launch_take", "device_put", "dispatch",
                               "harvest_wait"}
        assert len({sh for _s, _e, sh in phases.values()}) == 1
        assert phases["launch_take"][1] <= phases["device_put"][0] \
            <= phases["dispatch"][0] <= phases["harvest_wait"][0]
    assert {sh for p in by_launch.values() for _s, _e, sh in p.values()} \
        == {0, 1}
    assert sum(p == "ship_idle" for p, *_ in trace["wf"]) == 6
    assert sum(p == "native_bookkeeping" for p, *_ in trace["wf"]) == 4
    steps = [m for m in trace["modules"] if m[0].startswith("jit_wf_step")]
    assert len(steps) == 6
    assert {m[0].split("(")[0] for m in steps} == {"jit_wf_step_regular"}
    # and the reduction the benchmark already had names them the same way
    ex = trace_reduce.reduce_file(TRACE)["executables"]
    assert {k.split("(")[0] for k in ex} == {"jit_wf_step_regular"}


def brute_force(trace, floor):
    """The same rules, by sampling: every elementary interval between two
    neighbouring event edges is judged at its midpoint."""
    lo, hi = trace["span"]
    edges = {lo, hi}
    for s, e in trace["ops"]:
        edges.update((s, e))
    for _p, s, e, _l, _sh in trace["wf"]:
        edges.update((s, e))
    edges = sorted(edges)
    shards = sorted({sh for *_x, sh in trace["wf"] if sh is not None})
    first = {sh: min(s for _p, s, _e, _l, x in trace["wf"] if x == sh)
             for sh in shards}
    last = {sh: max(e for _p, _s, e, _l, x in trace["wf"] if x == sh)
            for sh in shards}

    def busy(t):
        return any(s <= t < e for s, e in trace["ops"])

    # idle stretches, to know each instant's stretch length
    stretches, start = [], None
    for a, b in zip(edges, edges[1:]):
        if not busy((a + b) / 2):
            start = a if start is None else start
        elif start is not None:
            stretches.append((start, a))
            start = None
    if start is not None:
        stretches.append((start, hi))
    out = {"idle": 0.0, "small": 0.0, "between": 0.0, "feeding": 0.0}
    for a, b in zip(edges, edges[1:]):
        t = (a + b) / 2
        if busy(t):
            continue
        out["idle"] += b - a
        s0, e0 = next((s, e) for s, e in stretches if s <= t < e)
        if e0 - s0 < floor:
            out["small"] += b - a
            continue
        open_by_shard = {
            sh: [p for p, s, e, _l, x in trace["wf"] if x == sh
                 and s <= t < e] for sh in shards}
        resting = all(
            t < first[sh] or t >= last[sh] or ia.IDLE in open_by_shard[sh]
            for sh in shards)
        if resting:
            out[ia.IDLE] = out.get(ia.IDLE, 0.0) + b - a
            if any(p == ia.FEEDING and s <= t < e
                   for p, s, e, _l, _x in trace["wf"]):
                out["feeding"] += b - a
            continue
        open_now = {p for p, s, e, _l, _x in trace["wf"] if s <= t < e}
        for phase in ia.PHASES:
            if phase in open_now:
                out[phase] = out.get(phase, 0.0) + b - a
                break
        else:
            out["between"] += b - a
    return out


def test_attribution_equals_a_brute_force_reading(trace):
    got = ia.attribute(trace)
    want = brute_force(trace, 2.0 * abs(got["clock_offset_ms"]) * 1e6)
    assert got["ship_threads"] == 2
    assert got["idle_s"] * 1e9 == pytest.approx(want["idle"], abs=1)
    assert (got["idle_s"] - got["attributable_s"]) * 1e9 \
        == pytest.approx(want["small"], abs=1)
    for phase, sec in got["by_phase_s"].items():
        assert sec * 1e9 == pytest.approx(want.get(phase, 0.0), abs=1), phase
    assert got["between_phases_s"] * 1e9 == pytest.approx(want["between"],
                                                          abs=1)
    assert got["starved_while_feeding_s"] * 1e9 \
        == pytest.approx(want["feeding"], abs=1)
    # the recording leaves both kinds of time: starved, and a ship thread
    # out of every phase (the 1.5 ms sleep before each harvest)
    assert got["by_phase_s"]["ship_idle"] > 0.002
    assert got["between_phases_s"] > 0.001
    # the device's and the host's clocks are within a few ms of each other
    assert abs(got["clock_offset_ms"]) < 5.0


def test_idle_is_what_the_old_reduction_calls_idle(trace):
    red = trace_reduce.reduce_file(TRACE)
    got = ia.attribute(trace)
    assert got["idle_s"] == pytest.approx(red["window_s"] - red["busy_s"],
                                          abs=1e-9)
