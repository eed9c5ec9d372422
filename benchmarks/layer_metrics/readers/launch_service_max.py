"""The longest launch service of the window, from the launch records: the
end of a launch's dispatch to the end of its harvest, and what its ship
thread was doing meanwhile."""

from layer_metrics.readers import launch_file


def _overlap(a0, a1, b0, b1):
    return max(0, min(a1, b1) - max(a0, b0))


def read(obs, params):
    records = launch_file.in_window(obs, launch_file.spans(obs) or [])
    by_launch = {}
    for r in records:
        if r["launch"] is not None:
            by_launch.setdefault(r["launch"], {})[r["phase"]] = r
    closed = [(p["harvest_wait"]["t1_ns"] - p["dispatch"]["t1_ns"], lid, p)
              for lid, p in by_launch.items()
              if "dispatch" in p and "harvest_wait" in p]
    if not closed:
        return None
    dt, lid, phases = max(closed, key=lambda c: c[0])
    disp, wait = phases["dispatch"], phases["harvest_wait"]
    # between the dispatch and the harvest: which phases its ship thread
    # was in; the harvest itself waits on the device if it was not ready
    held = {}
    for r in records:
        if r["shard"] == disp["shard"] and r["launch"] != lid:
            ov = _overlap(disp["t1_ns"], wait["t0_ns"],
                          r["t0_ns"], r["t1_ns"])
            if ov:
                held[r["phase"]] = held.get(r["phase"], 0) + ov
    held["harvest_wait"] = wait["t1_ns"] - wait["t0_ns"]
    parts = ", ".join(f"{p} {ns / 1e6:.3f}" for p, ns in
                      sorted(held.items(), key=lambda kv: -kv[1])[:4])
    return {"value": dt / 1e6,
            "note": f"launch {lid} on ship thread {disp['shard']}, fed by "
                    f"bookkeeping call {disp['cause']}, ready at harvest "
                    f"{wait.get('ready')}; its ship thread meanwhile (ms): "
                    f"{parts}; {len(closed)} launches in the window"}
