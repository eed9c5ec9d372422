"""How late the open-loop generator pushed, per chunk."""

import numpy as np


def read(obs, params):
    late = obs["gen"]["late_ms"]
    if late is None or not len(late):
        return None
    return float(np.percentile(late, params["percentile"]))
