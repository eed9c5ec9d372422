"""Busy share of the busiest receiving host node, from NodeStats."""


def read(obs, params):
    best = None
    for node in obs["nodes"]:
        alive = node.get("alive_sec", 0.0)
        if alive <= 0 or not node.get("rcv_batches"):
            continue
        share = 100.0 * node["svc_time_ms_total"] / 1e3 / alive
        if best is None or share > best[0]:
            best = (share, node["node"])
    if best is None:
        return None
    return {"value": best[0], "note": f"busiest node {best[1]}"}
