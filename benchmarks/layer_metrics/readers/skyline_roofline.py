"""The all-pairs skyline's share of the chip's vector peak over the traced
slice: the operations its windows need (``harness/ops_model_skyline.py``,
from the program's ``udf_rows`` and ``udf_windows`` counters) over the device
time of the step family that runs the user's function over the peak stated
in ``harness/peaks_vector.json``.  A program without the counters, a trace
without the family and a device without a stated peak give nothing to read."""

import json
import os

from harness import ops_model_skyline
from layer_metrics.readers.family_device import family_time

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(
    ops_model_skyline.__file__)), "peaks_vector.json")


def read(obs, params):
    trace, counters = obs["trace"], obs["slice_counters"]
    if trace is None or not counters.get("udf_windows"):
        return None
    seconds, launches = family_time(trace, params["family"])
    if not launches:
        return None
    import jax   # the run's own process: it holds the device it names
    with open(_PEAKS) as f:
        peak = json.load(f)["by_device_kind"].get(
            jax.devices()[0].device_kind)
    if peak is None:
        return None
    rows, windows = counters["udf_rows"], counters["udf_windows"]
    n_ops = ops_model_skyline.skyline_ops_at_least(rows, windows)
    share = ops_model_skyline.vector_share_pct(
        n_ops, seconds, peak["vector_op_per_s"])
    if share is None:
        return None
    return {"value": share,
            "note": f"{n_ops:.4g} operations needed ({windows:.0f} windows "
                    f"of {rows / windows:.0f} points, 9 a pair test), "
                    f"{seconds:.6f} s of the family's {launches} launches "
                    f"on the device, vector peak "
                    f"{peak['vector_op_per_s']:.4g} op/s (assumed, an upper "
                    f"bound)"}
