"""Device time of one launch: the step executables' seconds in the traced
slice over their launches, by the family their names give."""

from harness.idle_attribution import STEP_PREFIX


def read(obs, params):
    trace = obs["trace"]
    if trace is None:
        return None
    steps = {name.split("(")[0]: v for name, v in
             trace["executables"].items() if name.startswith(STEP_PREFIX)}
    launches = sum(n for _s, n in steps.values())
    if not launches:
        return None
    seconds = sum(s for s, _n in steps.values())
    return {"value": 1e3 * seconds / launches,
            "note": "; ".join(
                f"{name} {1e3 * s / n:.3f} ms x {n}" for name, (s, n)
                in sorted(steps.items(), key=lambda kv: -kv[1][0]))}
