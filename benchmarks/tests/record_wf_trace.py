"""Records the second small trace, the one that holds the program's own
``wf.`` annotations, for ``test_idle_attribution.py``.  Run on the chip,
once, by hand:

    chiprun -- python benchmarks/tests/record_wf_trace.py

Two threads play ship threads 0 and 1 through the program's own
``utils/profile.span`` (so the annotations are what the program writes) and
launch the program's own regular step, named by its family; the main thread
plays a window worker inside ``native_bookkeeping``.  The ``.xplane.pb`` is
copied to ``chiprun_out/wf_trace.xplane.pb`` and what the attribution reads
of it is printed.
"""

import os
import shutil
import sys
import threading
import time

import jax
import numpy as np

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.dirname(BENCH), BENCH):
    sys.path.insert(0, p)
from harness import idle_attribution, trace_reduce  # noqa: E402
from windflow_tpu.ops import resident  # noqa: E402
from windflow_tpu.utils import profile  # noqa: E402

KP, CAP, RB, C = 64, 4096, 256, 8


def ship_thread(shard, step, args, rounds, lead_s):
    time.sleep(lead_s)
    for _ in range(rounds):
        with profile.span("ship_idle", shard=shard):
            time.sleep(0.003)
        tag = (profile.next_id(), shard, None)
        with profile.span("launch_take", *tag):
            time.sleep(0.0004)
        with profile.span("device_put", *tag):
            dev = jax.device_put(args)
        with profile.span("dispatch", *tag):
            _ring, out = step(*dev)
        time.sleep(0.0015)          # out of ship_idle, in no named phase
        with profile.span("harvest_wait", *tag):
            np.asarray(out)


def main():
    if jax.devices()[0].platform != "tpu":
        print("needs a TPU")
        return 3
    step = resident._make_regular_step(
        ("reg", "sum", CAP, RB, KP, C, "<i2", "<i4", 64))
    args = (np.zeros((KP, CAP), np.int32), np.ones((KP, RB), np.int16),
            np.zeros(KP, np.int32), np.full(KP, C, np.int32),
            np.zeros(KP, np.int32), np.full(KP, 256, np.int32))
    jax.block_until_ready(step(*jax.device_put(args)))
    profile.enable()
    profile.reset()
    out = os.path.join("chiprun_out", "wf_trace")
    shutil.rmtree(out, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(out, profiler_options=opts)
    threads = [threading.Thread(target=ship_thread,
                                args=(shard, step, args, 3, lead))
               for shard, lead in ((0, 0.001), (1, 0.0035))]
    for th in threads:
        th.start()
    for _ in range(4):
        with profile.span("native_bookkeeping", cause=profile.next_id()):
            time.sleep(0.002)
        time.sleep(0.0025)
    for th in threads:
        th.join()
    jax.profiler.stop_trace()
    profile.auto()
    path = trace_reduce.find_xplane(out)
    shutil.copyfile(path, os.path.join("chiprun_out", "wf_trace.xplane.pb"))
    print("trace", path, os.path.getsize(path), "bytes")
    trace = idle_attribution.load(path)
    print("span", trace["span"])
    for name, s, e in trace["modules"]:
        print(f"  module {name[:60]} {s:.0f} {e:.0f}")
    for rec in sorted(trace["wf"], key=lambda r: r[1]):
        print("  wf", rec)
    print(idle_attribution.attribute(trace))
    print(trace_reduce.reduce_file(path)["executables"])
    shutil.rmtree(out, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
