"""The join step's share of the HBM roofline over the traced slice: the
bytes the windows it joined need (``harness/bytes_model_join.py``, from the
program's ``join_left_rows``, ``join_right_rows`` and ``join_results``
counters over the slice) over the device time of the step family that runs
the join over the chip's HBM peak.  A program without the counters, a slice
in which no window was joined and a trace without the family give nothing to
read."""

from harness import bytes_model, bytes_model_join
from layer_metrics.readers.family_device import family_time


def read(obs, params):
    trace, counters = obs["trace"], obs["slice_counters"]
    if trace is None or not counters.get("join_windows"):
        return None
    seconds, launches = family_time(trace, params["family"])
    if not launches:
        return None
    rows = counters.get("join_left_rows", 0.0) \
        + counters.get("join_right_rows", 0.0)
    parts = bytes_model_join.join_bytes(
        rows, counters.get("join_results", 0.0),
        ring_cols=int(params["ring_cols"]), out_cols=int(params["out_cols"]))
    n_bytes = sum(parts.values())
    share = bytes_model.hbm_share_pct(
        n_bytes, seconds, obs["peaks"]["hbm_bytes_per_s"])
    if share is None:
        return None
    return {"value": share,
            "note": f"{n_bytes:.0f} bytes needed ("
                    + ", ".join(f"{k} {v:.0f}" for k, v in parts.items())
                    + f") by {counters['join_windows']:.0f} windows of "
                    f"{rows:.0f} rows and {counters.get('join_results', 0):.0f}"
                    f" matches, {seconds:.6f} s of the family's {launches} "
                    f"launches on the device, HBM-bound"}
