"""The largest self or blocked share of a host node's life, from the
three-way split of NodeStats (sources included).  A source's service is
its whole ``generate()``; what the program did of it is the stages fused
into its thread, so that is a source's self here.  The rest is the source
function, which in a benchmark run is the load generator (it has its own
metrics, and in an open loop it sleeps)."""


def _shares(node):
    alive = node.get("alive_sec", 0.0) * 1e3
    if alive <= 0 or "self_ms_total" not in node:
        return None
    shares = {k: 100.0 * node[f"{k}_ms_total"] / alive
              for k in ("self", "blocked", "idle")}
    if not node.get("rcv_batches"):
        shares["self"] = 100.0 * sum(node["fused_svc_ms"].values()) / alive
    return shares


def read(obs, params):
    field, best = params["field"], None
    for node in obs["nodes"]:
        shares = _shares(node)
        if shares and (best is None or shares[field] > best[0][field]):
            best = (shares, node)
    if best is None:
        return None
    shares, node = best
    note = (f"{node['node']}: self {shares['self']:.1f}%, blocked "
            f"{shares['blocked']:.1f}%, idle {shares['idle']:.1f}%")
    if not node.get("rcv_batches"):
        note += " (a source: self is its fused stages, the rest generate())"
    if field == "blocked":
        note += (f"; its longest put {node['blocked_max_ms']:.3f} ms, on "
                 f"the inbox of {node['blocked_max_inbox']}")
    return {"value": shares[field], "note": note}
