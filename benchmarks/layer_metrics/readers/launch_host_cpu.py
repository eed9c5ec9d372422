"""CPU time of one launch on its ship thread: take + transfer + dispatch on
the thread's CPU clock, from the ``cpu_ns`` of the launch records, beside
their wall time.  CPU near wall: the thread copies and computes for those
milliseconds; CPU well under wall: it waits inside them.  A wait
(``harvest_wait``, ``ship_idle``) should burn none.  Where the CPU clock is
dear the program reads it on every so-manyth span of a phase: a phase's CPU
is then its wall time times the CPU share of the spans that carry the field.
Records without the field (the program before its spans read the CPU clock)
give nothing."""

from layer_metrics.readers import launch_file

PARTS = ("launch_take", "device_put", "dispatch")
WAITS = ("harvest_wait", "ship_idle")


def read(obs, params):
    records = launch_file.in_window(obs, launch_file.spans(obs) or [])
    launches = sum(r["phase"] == "dispatch" for r in records)
    wall = dict.fromkeys(PARTS + WAITS, 0)
    followed = {p: [0, 0, 0] for p in wall}     # spans, wall ns, CPU ns
    for r in records:
        if r["phase"] in wall:
            dt = r["t1_ns"] - r["t0_ns"]
            wall[r["phase"]] += dt
            if "cpu_ns" in r:
                f = followed[r["phase"]]
                f[0] += 1
                f[1] += dt
                f[2] += r["cpu_ns"]
    if not launches or not all(followed[p][1] for p in PARTS):
        return None
    cpu = {p: (wall[p] * f[2] / f[1] if f[1] else 0.0)
           for p, f in followed.items()}

    def per_launch(p):
        return (f"{p} {wall[p] / launches / 1e6:.3f} / "
                f"{cpu[p] / launches / 1e6:.3f}")

    return {"value": sum(cpu[p] for p in PARTS) / launches / 1e6,
            "note": "wall / CPU ms a launch: "
                    + ", ".join(per_launch(p) for p in PARTS)
                    + "; the waits: " + ", ".join(per_launch(p) for p in WAITS)
                    + f"; mean over {launches} launches of the window, the "
                    "CPU clock on "
                    + " / ".join(str(followed[p][0]) for p in PARTS)
                    + " spans of the three parts"}
