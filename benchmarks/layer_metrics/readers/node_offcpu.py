"""A host node's off-CPU time: inside its own work -- the "self" of the
three-way split of NodeStats -- and not running.  The program reads the
thread's CPU clock where it reads the wall clock, so self minus self-CPU is
the time the thread waited while in service: for the interpreter lock, for
a lock of the program's, in a sleep, or for its core.  The thread's switch
counts tell the causes apart: a wait is a voluntary switch, being pushed
off the core an involuntary one.  A source's service is all of
``generate()``, which in a benchmark run is the load generator (it sleeps
in an open loop); what the program did of it is the stages fused into the
source's thread, so a source counts with those alone.  ``over: node`` gives
the largest share of a node's life, ``over: graph`` the share of all the
nodes' self time.  A log without the CPU clock (the program before it had
one) gives nothing."""


def _row(node):
    alive = node.get("alive_sec", 0.0) * 1e3
    if alive <= 0 or "self_cpu_ms_total" not in node:
        return None
    if node.get("rcv_batches"):
        self_ms, cpu_ms = node["self_ms_total"], node["self_cpu_ms_total"]
    else:
        self_ms = sum(node["fused_svc_ms"].values())
        cpu_ms = sum(node["fused_cpu_ms"].values())
    return {"log": node, "alive": alive, "self": self_ms, "cpu": cpu_ms,
            "off": self_ms - cpu_ms}


def _line(row):
    log, alive = row["log"], row["alive"]
    pct = {k: 100.0 * v / alive for k, v in (
        ("self", row["self"]), ("cpu", row["cpu"]), ("off", row["off"]),
        ("blocked", log["blocked_ms_total"]), ("idle", log["idle_ms_total"]),
        ("wait_cpu", log["wait_cpu_ms_total"]))}
    voluntary = log.get("ctx_voluntary", 0)
    per_switch = (f"{row['off'] / voluntary:.3f} ms off the CPU per "
                  f"voluntary switch" if voluntary else "no voluntary switch")
    return (f"{log['node']}: self {pct['self']:.1f}% = on the CPU "
            f"{pct['cpu']:.1f}% + off it {pct['off']:.1f}%, blocked "
            f"{pct['blocked']:.1f}%, idle {pct['idle']:.1f}% (CPU burnt "
            f"waiting {pct['wait_cpu']:.2f}%); switches {voluntary} "
            f"voluntary / {log.get('ctx_involuntary', 0)} involuntary, "
            f"{per_switch}"
            + ("" if log.get("rcv_batches") else " (a source: its fused "
               "stages only)"))


def read(obs, params):
    rows = [r for r in map(_row, obs["nodes"]) if r]
    if not rows:
        return None
    if params["over"] == "graph":
        total = sum(r["self"] for r in rows)
        if total <= 0:
            return None
        off = sum(r["off"] for r in rows)
        top = sorted(rows, key=lambda r: -r["off"])[:3]
        wait_cpu, burnt = max(
            (100.0 * r["log"]["wait_cpu_ms_total"] / r["alive"],
             r["log"]["node"]) for r in rows)
        return {"value": 100.0 * off / total,
                "note": f"{off:.1f} ms off the CPU of {total:.1f} ms self "
                        f"over {len(rows)} nodes; most of it: "
                        + ", ".join(f"{r['log']['node']} {r['off']:.1f} ms"
                                    for r in top)
                        + f"; most CPU burnt waiting: {burnt} "
                        f"{wait_cpu:.2f}% of its life"}
    worst = max(rows, key=lambda r: r["off"] / r["alive"])
    busiest = max(rows, key=lambda r: r["self"] / r["alive"])
    note = _line(worst)
    if busiest is not worst:
        note += "; the node with the largest self: " + _line(busiest)
    return {"value": 100.0 * worst["off"] / worst["alive"], "note": note}
