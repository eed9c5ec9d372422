"""Share of the launches whose result was there before their harvest
began: their service beyond host and device time was waiting to be polled."""

from layer_metrics.readers import launch_file


def read(obs, params):
    counters = obs["slice_counters"]
    launches = counters.get("launches")
    if not launches:
        return None
    ready = counters.get("launches_ready_at_poll", 0.0)
    note = f"{ready:.0f} of {launches:.0f} launches harvested in the slice"
    waited = [r for r in launch_file.in_window(
        obs, launch_file.spans(obs) or [])
        if r["phase"] == "harvest_wait" and r.get("ready") is False]
    if waited:
        mean = sum(r["t1_ns"] - r["t0_ns"] for r in waited) / len(waited)
        note += (f"; the window's {len(waited)} others blocked "
                 f"{mean / 1e6:.3f} ms each in harvest_wait")
    else:
        note += "; none blocked in harvest_wait in the window"
    return {"value": 100.0 * ready / launches, "note": note}
