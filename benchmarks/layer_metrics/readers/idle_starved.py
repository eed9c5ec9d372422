"""Share of the device's idle time in which every ship thread had nothing
to launch."""

import os

from harness import idle_attribution, trace_reduce
from layer_metrics.readers import launch_file


def read(obs, params):
    if obs["trace"] is None:
        return None
    try:
        path = trace_reduce.find_xplane(
            os.path.join(launch_file.out_dir(obs), "trace"))
    except FileNotFoundError:
        return None
    got = idle_attribution.attribute(idle_attribution.load(path))
    if got is None or got["idle_s"] <= 0:
        return None
    by = got["by_phase_s"]
    named = sum(by.values())
    phases = ", ".join(f"wf.{p} {s:.4f}" for p, s in by.items())
    return {"value": 100.0 * by[idle_attribution.IDLE] / got["idle_s"],
            "note": f"idle {got['idle_s']:.4f} s: {phases}, between phases "
                    f"{got['between_phases_s']:.4f}, in stretches under "
                    f"twice the clock offset "
                    f"{got['idle_s'] - got['attributable_s']:.4f}; "
                    f"{100.0 * named / got['idle_s']:.1f}% to a wf. phase; "
                    f"of the starved seconds "
                    f"{got['starved_while_feeding_s']:.4f} with a worker in "
                    f"wf.native_bookkeeping; device - host clock "
                    f"{got['clock_offset_ms']:.3f} ms; "
                    f"{got['ship_threads']} ship threads"}
