"""The self share of the life of the nodes whose name holds
``params["name_has"]`` (a stage's workers, by the stage's name), from the
three-way split of NodeStats: the largest of them.  A program without such a
node reports nothing."""

from layer_metrics.readers.node_split import _shares


def read(obs, params):
    best = None
    for node in obs["nodes"]:
        if params["name_has"] not in node.get("node", ""):
            continue
        shares = _shares(node)
        if shares and node.get("rcv_batches") \
                and (best is None or shares["self"] > best[0]["self"]):
            best = (shares, node)
    if best is None:
        return None
    shares, node = best
    calls = node["rcv_batches"]
    return {"value": shares["self"],
            "note": f"{node['node']}: self {shares['self']:.2f}%, blocked "
                    f"{shares['blocked']:.2f}%, idle {shares['idle']:.2f}%; "
                    f"{node['self_ms_total'] / calls:.3f} ms a batch over "
                    f"{calls} batches"}
