"""The arg-extremum family's share of the HBM roofline over the traced
slice: the bytes its launches need (``harness/bytes_model_argext.py``) over
the device time of its own executables over the chip's HBM peak."""

from harness import bytes_model, bytes_model_argext
from layer_metrics.readers.family_device import family_time


def read(obs, params):
    trace, counters = obs["trace"], obs["slice_counters"]
    if trace is None or not counters or "eval_rows" not in counters:
        return None
    seconds, launches = family_time(trace, params["family"])
    if not launches:
        return None
    n_bytes = bytes_model_argext.argext_bytes(
        counters.get("bytes_shipped", 0.0), counters["eval_rows"],
        counters.get("eval_windows", 0.0))
    share = bytes_model.hbm_share_pct(
        n_bytes, seconds, obs["peaks"]["hbm_bytes_per_s"])
    if share is None:
        return None
    return {"value": share,
            "note": f"{n_bytes:.0f} bytes needed ({counters['eval_rows']:.0f} "
                    f"ring cells in {counters.get('eval_windows', 0):.0f} "
                    f"windows evaluated), {seconds:.6f} s of the family's "
                    f"{launches} launches on the device, HBM-bound"}
