"""Records the small trace that ``test_trace_reduce.py`` checks the reduction
on.  Run on the chip, once, by hand:

    chiprun -- python benchmarks/tests/record_small_trace.py

Two jitted programs of different names run a few times each with sleeps in
between, under the benchmark's own annotations; the ``.xplane.pb`` is copied
to ``chiprun_out/small_trace.xplane.pb`` and its planes, lines and events are
printed, so that the expected numbers can be computed by hand.
"""

import glob
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from harness import trace_reduce  # noqa: E402


def main():
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("needs a TPU")
        return 3

    @jax.jit
    def small_cumsum(x):
        return jnp.cumsum(x, axis=1)

    @jax.jit
    def small_scale(x):
        return x * 3 + 1

    x = jnp.ones((64, 8192), dtype=jnp.int32)
    small_cumsum(x).block_until_ready()
    small_scale(x).block_until_ready()
    out = os.path.join("chiprun_out", "small_trace")
    shutil.rmtree(out, ignore_errors=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(out, profiler_options=opts)
    for i in range(3):
        with jax.profiler.TraceAnnotation("bench.gen_blocked_in_push"):
            small_cumsum(x).block_until_ready()
            time.sleep(0.004)
        with jax.profiler.TraceAnnotation("bench.sink_consume"):
            small_scale(x).block_until_ready()
            time.sleep(0.002)
        time.sleep(0.003)
    jax.profiler.stop_trace()
    path = trace_reduce.find_xplane(out)
    dst = os.path.join("chiprun_out", "small_trace.xplane.pb")
    shutil.copyfile(path, dst)
    print("trace", path, os.path.getsize(path), "bytes")
    planes = trace_reduce.load(path)
    for pname, lines in planes.items():
        print("PLANE", pname)
        for lname, events in lines.items():
            print("  LINE", lname, len(events))
            for name, s, e in events[:12]:
                print(f"      {name[:90]} start={s:.0f} end={e:.0f}")
    print(trace_reduce.reduce_planes(planes))
    shutil.rmtree(out, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
