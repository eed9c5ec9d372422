"""Mean launch service: dispatch to harvest, the harvest poll included."""


def read(obs, params):
    res = obs["resident"]
    if not res.get("dispatches"):
        return None
    return {"value": float(res["mean_launch_ms"]),
            "note": f"{res['dispatches']} dispatches, {res.get('merges', 0)} "
                    f"merges; stamped at harvest, so it includes waiting to "
                    f"be polled"}
