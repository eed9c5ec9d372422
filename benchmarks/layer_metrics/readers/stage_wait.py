"""What the second stage of a Pane_Farm adds to a result's wait, from the
run's launch records (``launches.jsonl``): the program writes one
``pane_emit`` record as a batch of pane results leaves its pane-stage worker
and one ``window_emit`` record around the hand-over of a batch of window
results (the sink chained to the window stage runs inside it), each with the
result ids it carried.  Window ``w`` is complete with its last pane,
``w * slide/pane + win/pane - 1``: the wait is from the start of that pane's
``pane_emit`` to the end of the window's ``window_emit``, the median over
the windows the measured window closed.  The window's geometry is the
configuration's (``shapes.win_us``, ``shapes.slide_us``).  A program without
the records reports nothing."""

import math

import numpy as np

from layer_metrics.readers import launch_file


def _by_id(records, phase):
    out = {}
    for r in records:
        if r["phase"] == phase and "ids" in r:
            for i in r["ids"]:
                out.setdefault(int(i), r)
    return out


def read(obs, params):
    records = launch_file.in_window(obs, launch_file.spans(obs) or [])
    panes, windows = _by_id(records, "pane_emit"), \
        _by_id(records, "window_emit")
    if not panes or not windows:
        return None
    shp = obs["cfg"]["shapes"]
    pane = math.gcd(int(shp["win_us"]), int(shp["slide_us"]))
    per_win, per_slide = int(shp["win_us"]) // pane, \
        int(shp["slide_us"]) // pane
    waits, next_pane = [], []
    for w, rec in windows.items():
        last = panes.get(w * per_slide + per_win - 1)
        if last is None:
            continue
        waits.append((rec["t1_ns"] - last["t0_ns"]) / 1e6)
        after = panes.get(w * per_slide + per_win)
        if after is not None:
            next_pane.append((rec["t1_ns"] - after["t0_ns"]) / 1e6)
    if not waits:
        return None
    p25, p50, p75 = np.percentile(waits, [25, 50, 75])
    note = (f"{len(waits)} windows: last pane's result emitted -> window's "
            f"result handed on p25/p50/p75 {p25:.3f}/{p50:.3f}/{p75:.3f} ms "
            f"({per_win} panes of {pane} us a window)")
    if next_pane:
        note += (f"; from the NEXT pane's result, which closes a "
                 f"count-based window, p50 {np.percentile(next_pane, 50):.3f}"
                 f" ms")
    return {"value": float(p50), "note": note}
