"""Host time of one launch on its ship thread: take + transfer + dispatch."""

PARTS = ("launch_take", "device_put", "dispatch")


def read(obs, params):
    spans = obs["profile_spans"]
    if any(p not in spans for p in PARTS):
        return None
    launches = spans["dispatch"][1]
    if not launches:
        return None
    ms = {p: 1e3 * spans[p][0] / launches for p in PARTS}
    return {"value": sum(ms.values()),
            "note": " + ".join(f"{p} {ms[p]:.3f}" for p in PARTS)
                    + f" ms, mean over {launches} launches of the window"}
