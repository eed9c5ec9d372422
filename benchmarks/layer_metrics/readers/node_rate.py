"""A counter of the nodes' NodeStats files a second of the reporting nodes'
life: its sum over the nodes that report it over the longest of their lives
(``alive_sec``).  A program whose nodes do not report the counter reports
nothing."""


def read(obs, params):
    nodes = [n for n in obs["nodes"]
             if params["counter"] in n and n.get("alive_sec", 0.0) > 0]
    if not nodes:
        return None
    total = sum(float(n[params["counter"]]) for n in nodes)
    alive = max(float(n["alive_sec"]) for n in nodes)
    return {"value": total / alive,
            "note": f"{total:.0f} over {alive:.3f} s of "
                    f"{', '.join(n['node'] for n in nodes)}"}
