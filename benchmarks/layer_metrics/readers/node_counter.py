"""A counter of the nodes' NodeStats files (``out/<cell>/nodes``): summed or
its maximum over the nodes that report it, or the largest node's share of its
sum.  ``among`` names another counter a node has to report to be counted
(``rcv_tuples`` is every node's).  A program whose nodes do not report the
counter reports nothing."""


def read(obs, params):
    among = params.get("among", params["counter"])
    nodes = [n for n in obs["nodes"]
             if among in n and params["counter"] in n]
    if not nodes:
        return None
    values = [float(n[params["counter"]]) for n in nodes]
    how = params["how"]
    if how == "max_share":
        if sum(values) <= 0:
            return None
        value = 100.0 * max(values) / sum(values)
    else:
        value = {"sum": sum, "max": max}[how](values)
    top = nodes[values.index(max(values))]
    return {"value": value,
            "note": f"{len(nodes)} nodes, the largest {top['node']}: "
                    f"{max(values):.0f}"}
