"""Idle share of the device over the traced slice."""


def read(obs, params):
    trace = obs["trace"]
    if trace is None or trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - trace["busy_s"] / trace["window_s"])
