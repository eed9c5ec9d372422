"""How full the slots of a container-valued device result are: the slots
that hold something (NodeStats ``pane_points_kept``) over the results'
slots (``pane_results`` x the configuration's ``shapes[params["cap"]]``),
summed over the nodes that report both.  A program whose nodes do not report
them reports nothing."""


def read(obs, params):
    nodes = [n for n in obs["nodes"]
             if "pane_points_kept" in n and "pane_results" in n]
    results = sum(float(n["pane_results"]) for n in nodes)
    if results <= 0:
        return None
    kept = sum(float(n["pane_points_kept"]) for n in nodes)
    cap = int(obs["cfg"]["shapes"][params["cap"]])
    over = sum(float(n.get("pane_overflow", 0)) for n in nodes)
    return {"value": 100.0 * kept / (results * cap),
            "note": f"{kept:.0f} slots held in {results:.0f} results of "
                    f"{cap} slots over {len(nodes)} nodes, "
                    f"{kept / results:.2f} a result; {over:.0f} results over "
                    f"their cap"}
