"""Event-time latency over every result of the run."""

import numpy as np


def read(obs, params):
    lat = obs["latency_ms"]
    if lat is None or not len(lat):
        return None
    return float(np.percentile(lat, params["percentile"]))
