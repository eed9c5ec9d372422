"""Device time of one launch of ONE step family: the seconds of the
executables named ``jit_wf_step_<family>`` in the traced slice over their
launches.  ``params["family"]`` names the family; a trace without it (a
program that lacks the family) gives nothing to read."""

from harness.idle_attribution import STEP_PREFIX


def family_time(trace, family):
    """``(seconds, launches)`` of the family's executables in the slice."""
    name = f"{STEP_PREFIX}_{family}"
    seconds, launches = 0.0, 0
    for full, (s, n) in trace["executables"].items():
        if full.split("(")[0] == name:
            seconds += s
            launches += n
    return seconds, launches


def read(obs, params):
    trace = obs["trace"]
    if trace is None:
        return None
    seconds, launches = family_time(trace, params["family"])
    if not launches:
        return None
    return {"value": 1e3 * seconds / launches,
            "note": f"{params['family']}: {seconds:.6f} s on the device in "
                    f"{launches} launches of the slice"}
