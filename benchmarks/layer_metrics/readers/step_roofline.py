"""The step executables' share of the HBM roofline over the traced slice."""

from harness import bytes_model


def read(obs, params):
    trace, counters = obs["trace"], obs["slice_counters"]
    if trace is None or not counters:
        return None
    seconds = sum(s for s, _n in trace["executables"].values())
    n_bytes = bytes_model.step_bytes(
        counters.get("bytes_shipped", 0.0), counters.get("windows", 0.0),
        obs["n_stats"])
    share = bytes_model.hbm_share_pct(
        n_bytes, seconds, obs["peaks"]["hbm_bytes_per_s"])
    if share is None:
        return None
    return {"value": share,
            "note": f"{n_bytes:.0f} bytes needed, {seconds:.6f} s of step "
                    f"executables on the device, HBM-bound"}
