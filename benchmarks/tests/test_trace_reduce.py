"""The reduction from a trace to numbers, on the small trace recorded on a
TPU v5 lite by ``record_small_trace.py``: three launches each of two jitted
programs, under the benchmark's two annotations.  The expected numbers were
worked out by hand from the trace's printed events (nanoseconds):

XLA Modules   small_cumsum  49331765-49371706, 61488204-61528101,
                            73564712-73604253          = 39941+39897+39541
              small_scale   54871763-54878484, 66930329-66937109,
                            79016959-79023697          =  6721+ 6780+ 6738
XLA Ops       first cumsum  49331772-49371705 with holes of 1+2+1 ns = 39929
              second        61488210-61528099 with holes of 1+2 ns   = 39886
              third         73564719-73604251 with holes of 1+1+2 ns = 39528
              the scales    6717 + 6777 + 6734                      = 20228
trace span    49331765 (first device event) .. 83369018 (last host event)
"""

import os

import numpy as np
import pytest

from conftest import BENCH
from harness import peaks, trace_reduce

TRACE = os.path.join(BENCH, "harness", "testdata", "small_trace.xplane.pb")


@pytest.fixture(scope="module")
def planes():
    return trace_reduce.load(TRACE)


@pytest.fixture(scope="module")
def reduced(planes):
    return trace_reduce.reduce_planes(planes)


def test_busy_idle_and_window(reduced):
    assert reduced["n_devices"] == 1
    assert round(reduced["busy_s"] * 1e9) == 39929 + 39886 + 39528 + 20228
    assert round(reduced["window_s"] * 1e9) == 83369018 - 49331765
    idle = 1 - reduced["busy_s"] / reduced["window_s"]
    assert abs(idle - 0.995899) < 1e-6


def test_busy_equals_a_brute_force_timeline(planes, reduced):
    ops = planes["/device:TPU:0"]["XLA Ops"]
    lo = int(min(s for _, s, _ in ops))
    hi = int(max(e for _, _, e in ops))
    line = np.zeros(hi - lo, dtype=bool)
    for _, s, e in ops:
        line[int(s) - lo:int(e) - lo] = True
    assert int(line.sum()) == round(reduced["busy_s"] * 1e9)


def test_per_executable_device_time(reduced):
    ex = {k.split("(")[0]: v for k, v in reduced["executables"].items()}
    assert set(ex) == {"jit_small_cumsum", "jit_small_scale"}
    assert round(ex["jit_small_cumsum"][0] * 1e9) == 39941 + 39897 + 39541
    assert round(ex["jit_small_scale"][0] * 1e9) == 6721 + 6780 + 6738
    assert ex["jit_small_cumsum"][1] == ex["jit_small_scale"][1] == 3


def test_device_ops_by_name(reduced):
    ops = dict(reduced["device_ops"])
    assert reduced["device_ops"][0][0] == "%reduce-window"
    assert round(ops["%multiply_add_fusion"] * 1e9) == 6717 + 6777 + 6734
    assert all(" = " not in name for name in ops)


def test_longest_gaps_and_what_the_host_was_doing(reduced):
    gaps = reduced["idle_gaps"]
    assert len(gaps) == 10
    # the longest: after the second scale until the third cumsum; the host
    # spent 1.08 ms of it still in the push annotation and 3.26 ms in the
    # sink's, so the sink's overlaps it most
    assert gaps[0][0] == "bench.sink_consume"
    assert round(gaps[0][1] * 1e9) == 73564719 - 66937109
    assert gaps[1][0] == "bench.sink_consume"
    assert round(gaps[1][1] * 1e9) == 61488210 - 54878482
    # after each cumsum the host is still inside the push annotation
    assert gaps[2][0] == "bench.gen_blocked_in_push"
    assert round(gaps[2][1] * 1e9) == 54871765 - 49371705
    assert [g[1] for g in gaps] == sorted((g[1] for g in gaps), reverse=True)
    # the trailing stretch, from the last op to the end of the trace
    assert ["bench.sink_consume", (83369018 - 79023696) / 1e9] in gaps
    # the nanosecond holes between the ops of one program belong to nobody
    assert gaps[-1][0] == "unattributed" and gaps[-1][1] < 1e-8


def test_union_merges_overlaps():
    assert trace_reduce.union([(5, 7), (0, 2), (1, 3), (7, 8), (10, 11)]) \
        == [(0, 3), (5, 8), (10, 11)]


def test_unknown_device_kind_is_an_error():
    assert peaks.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v9 imaginary")


# -- who an idle gap is named after -------------------------------------------

def _planes(host_events):
    """One chip busy 0-10, 110-120, 200-210, 300-310 and 400-401 (ns), so the
    gaps are 10-110, 120-200, 210-300 and 310-400; the host as given."""
    busy = [(0, 10), (110, 120), (200, 210), (300, 310), (400, 401)]
    return {"/device:TPU:0": {"XLA Ops": [("%op = x", float(s), float(e))
                                          for s, e in busy]},
            "/host:CPU": {"python": [(n, float(s), float(e))
                                     for n, s, e in host_events]}}


def test_a_gap_is_named_by_the_programs_phase_where_one_overlaps_it():
    got = trace_reduce.reduce_planes(_planes([
        # the generator is inside its push all along, as in a closed loop
        ("bench.gen_blocked_in_push", 0, 400),
        # gap 10-110: two phases of the program, the second covers more
        ("wf.launch_take", 10, 30), ("wf.device_put", 30, 100),
        # gap 120-200: one phase covers 5 ns of it, the push all 80: the
        # program's phase names it all the same
        ("wf.harvest_wait", 150, 155),
        # gap 210-300: two events of one phase add up against a longer one
        ("wf.ship_idle", 210, 240), ("wf.ship_idle", 260, 290),
        ("wf.native_bookkeeping", 240, 260), ("wf.dispatch", 292, 299),
        # gap 310-400: nothing of the program: the benchmark's own
        ("bench.sink_consume", 330, 340),
        ("PjitFunction(step)", 310, 400)]))["idle_gaps"]
    assert got[:4] == [["wf.device_put", 100e-9], ["wf.ship_idle", 90e-9],
                       ["bench.gen_blocked_in_push", 90e-9],
                       ["wf.harvest_wait", 80e-9]]


def test_a_gap_nothing_overlaps_belongs_to_nobody():
    got = trace_reduce.reduce_planes(_planes(
        [("wf.dispatch", 0, 5), ("bench.sink_consume", 401, 500)]))
    # the trailing stretch, after the last operation, is the sink's; the
    # four gaps between operations are nobody's
    assert sorted(got["idle_gaps"]) == sorted(
        [["bench.sink_consume", 99e-9], ["unattributed", 100e-9],
         ["unattributed", 80e-9], ["unattributed", 90e-9],
         ["unattributed", 90e-9]])


def test_the_recorded_ship_phases_name_the_gaps_of_their_trace():
    """On the trace that holds the program's own ``wf.`` annotations
    (``record_wf_trace.py``), against a plain loop over its events."""
    path = os.path.join(BENCH, "harness", "testdata", "wf_trace.xplane.pb")
    planes = trace_reduce.load(path)
    gaps = trace_reduce.reduce_planes(planes)["idle_gaps"]
    assert all(name.startswith("wf.") for name, _ in gaps)
    ops = sorted((s, e) for _, s, e in planes["/device:TPU:0"]["XLA Ops"])
    host = [ev for pname, lines in planes.items() if "device" not in pname
            for events in lines.values() for ev in events
            if ev[0].startswith("wf.")]
    # the longest gap, found again the slow way; the stretch before the
    # first operation and the one after the last count as gaps
    every = [ev for lines in planes.values() for events in lines.values()
             for ev in events]
    t_lo, t_hi = min(s for _, s, _ in every), max(e for _, _, e in every)
    edge, longest = t_lo, (0.0, 0.0)
    for s, e in ops + [(t_hi, t_hi)]:
        if s - edge > longest[1] - longest[0]:
            longest = (edge, s)
        edge = max(edge, e)
    assert round((longest[1] - longest[0])) == round(gaps[0][1] * 1e9)
    cover = {}
    for name, s, e in host:
        ov = min(e, longest[1]) - max(s, longest[0])
        if ov > 0:
            cover[name] = cover.get(name, 0.0) + ov
    assert gaps[0][0] == max(cover, key=cover.get) == "wf.ship_idle"
