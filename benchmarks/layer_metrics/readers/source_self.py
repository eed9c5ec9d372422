"""The source thread's time inside the stages fused into it."""


def read(obs, params):
    sources = [n for n in obs["nodes"]
               if "fused_svc_ms" in n and not n.get("rcv_batches")
               and n.get("alive_sec", 0.0) > 0]
    if not sources:
        return None
    node = max(sources, key=lambda n: sum(n["fused_svc_ms"].values()))
    alive = node["alive_sec"] * 1e3
    fused = node["fused_svc_ms"]
    stages = ", ".join(f"{k} {100.0 * v / alive:.1f}%"
                       for k, v in sorted(fused.items(), key=lambda kv: -kv[1]))
    return {"value": 100.0 * sum(fused.values()) / alive,
            "note": f"{node['node']}: {stages or 'no fused stage'}; blocked "
                    f"{100.0 * node['blocked_ms_total'] / alive:.1f}% "
                    f"(longest put {node['blocked_max_ms']:.3f} ms on "
                    f"{node['blocked_max_inbox']}); the rest is generate()"}
