"""One counter of the nodes' NodeStats files (``out/<cell>/nodes``) over
another, as a share: both summed over the nodes that report the first.  A
program whose nodes do not report it reports nothing."""


def read(obs, params):
    nodes = [n for n in obs["nodes"]
             if params["counter"] in n and params["over"] in n]
    if not nodes:
        return None
    part = sum(float(n[params["counter"]]) for n in nodes)
    whole = sum(float(n[params["over"]]) for n in nodes)
    if whole <= 0:
        return None
    return {"value": 100.0 * part / whole,
            "note": f"{part:.0f} of {whole:.0f} over {len(nodes)} nodes"}
