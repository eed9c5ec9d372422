"""One of the program's resident launch diagnostics
(``ops/resident.stats_snapshot()``: always on, reset as the measured pipeline
starts, read as the window closes), by name.  A program that does not keep
the counter reports nothing."""


def read(obs, params):
    value = obs["resident"].get(params["counter"])
    if value is None:
        return None
    return float(value)
