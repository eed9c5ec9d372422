#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything is resolved by name: ``workloads/<cell>.json`` names a
configuration (``configs/<config>.json``, ``.py``, ``_oracle.py``) and a
traffic mix (``traffic/<mix>.json``); ``BENCHMARK.json`` says which metrics
the cell reports, and each metric is ``e2e_metrics/<metric>.json`` or
``layer_metrics/<metric>.json`` with a reader module beside it.  This file
holds no cell's, configuration's or metric's name.

One process per run: load, warm up, measure ``--seconds``, drain, check,
print.  The last line of standard output is one JSON object with the keys
``correct, attempted, failed, metrics, device`` (and ``breakdown`` in a traced
run on a chip), then ``check``: each number compared beside its limit, which
are also the last lines of standard error.  Without a TPU the run exits
non-zero, unless ``JAX_PLATFORMS=cpu`` is set on purpose: then it rehearses
at a tiny size, says ``cpu`` in ``device`` and reports no trace-derived
metric.
"""

from __future__ import annotations

import os
import time

_CLOCK0 = time.perf_counter()


def _process_age_s():
    """Seconds since the kernel started this process (10 ms ticks)."""
    try:
        with open("/proc/self/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        ticks = int(fields[19])
        return (time.clock_gettime(time.CLOCK_BOOTTIME)
                - ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError, AttributeError):
        return 0.0


_AGE0 = max(_process_age_s(), 0.0)

import argparse          # noqa: E402
import contextlib        # noqa: E402
import importlib         # noqa: E402
import json              # noqa: E402
import shutil            # noqa: E402
import sys               # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
for _p in (ROOT, BENCH_DIR):
    if _p not in sys.path:
        sys.path.insert(0, _p)

EXIT_NO_DEVICE = 3
EXIT_DEVICE_PATH = 4


def _load_json(*parts):
    with open(os.path.join(BENCH_DIR, *parts)) as f:
        return json.load(f)


def _merge(base, over):
    out = dict(base)
    for k, v in (over or {}).items():
        out[k] = _merge(base[k], v) if isinstance(v, dict) \
            and isinstance(base.get(k), dict) else v
    return out


def resolve(cell_name, rehearsal):
    """The cell, its configuration, its mix and its metric lists."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    entry = next((w for w in manifest["workloads"]
                  if w["name"] == cell_name), None)
    if entry is None:
        raise SystemExit(f"BENCHMARK.json has no workload {cell_name!r}")
    cell = _load_json("workloads", f"{cell_name}.json")
    for key in ("config", "traffic", "chips"):
        if cell[key] != entry[key]:
            raise SystemExit(
                f"workloads/{cell_name}.json and BENCHMARK.json differ on "
                f"{key}: {cell[key]!r} != {entry[key]!r}")
    cfg = _load_json("configs", f"{cell['config']}.json")
    mix = _load_json("traffic", f"{cell['traffic']}.json")
    if rehearsal:
        cell = _merge(cell, cell.get("rehearsal"))
        cfg = _merge(cfg, cfg.get("rehearsal"))

    def listed(kind):
        return [m["name"] for m in manifest[kind]
                if cell_name in m.get("workloads", [cell_name])]

    return cell, cfg, mix, listed("end_to_end"), listed("per_layer")


def read_metrics(kind, names, obs, say):
    """``{name: {"value", "unit"}}`` for the metrics whose reader finds
    something to read."""
    out = {}
    for name in names:
        spec = _load_json(kind, f"{name}.json")
        reader = importlib.import_module(f"{kind}.readers.{spec['reader']}")
        got = reader.read(obs, spec.get("params", {}))
        if isinstance(got, dict):
            say(f"metric {name}: {got['note']}")
            got = got["value"]
        if got is None:
            say(f"metric {name}: nothing to read, left out")
            continue
        out[name] = {"value": got, "unit": spec["unit"]}
    return out


class SinkRecorder:
    """The benchmark's sink: keeps every result row with its arrival time."""

    def __init__(self, annotate=None):
        self.rows = []
        self.t_ns = []
        self.annotate = annotate

    def __call__(self, rows):
        if rows is None or not len(rows):
            return
        with (self.annotate("bench.sink_consume")
              if self.annotate else contextlib.nullcontext()):
            self.t_ns.append(time.monotonic_ns())
            self.rows.append(rows.copy())


def _step_shapes(resident):
    """Keys of the program's compiled-step cache (a private name: only for
    the note on shapes first used inside the window)."""
    return set(getattr(resident, "_STEP_CACHE", ()))


def trace_slice(jax, profile, gen, spec, seconds, trace_dir):
    """Trace the mix's slice of the running window with ``jax.profiler``;
    returns what the program's counters added during it."""
    while gen.log.t0_ns is None:
        time.sleep(0.001)
    start_ns = gen.log.t0_ns + int(spec["start_frac"] * seconds * 1e9)
    time.sleep(max(0.0, (start_ns - time.monotonic_ns()) / 1e9))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    before = profile.counters()
    time.sleep(min(float(spec["seconds"]), 0.25 * seconds))
    after = profile.counters()
    jax.profiler.stop_trace()
    return {k: after[k] - before.get(k, 0.0) for k in after}


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None,
                    help="for the rate sweep only: offered rate in place of "
                         "the cell's (the driver never passes it)")
    return ap.parse_args(argv)


def measure(args, say):
    """One run.  Returns ``(exit code, result line or None, detail)``;
    ``detail`` holds what the control script compares again."""
    marks = [("process start", -_AGE0)]

    def mark(label):
        marks.append((label, time.perf_counter() - _CLOCK0))

    import jax
    import numpy as np
    from harness import (check, device_assert, generator, host_allocator,
                         peaks, trace_reduce)
    from harness.compile_counter import CompileCounter

    devs = jax.devices()
    platform = devs[0].platform
    # a rehearsal is a run that JAX_PLATFORMS put on the CPU on purpose
    rehearsal = platform == "cpu" and "cpu" in os.environ.get(
        "JAX_PLATFORMS", "").split(",")
    if platform != "tpu" and not rehearsal:
        say(f"no TPU: JAX's default backend is {platform!r}; set "
            f"JAX_PLATFORMS=cpu to rehearse on the CPU on purpose")
        return EXIT_NO_DEVICE, None, None
    cell, cfg, mix, e2e_names, layer_names = resolve(args.workload, rehearsal)
    if platform == "tpu" and len(devs) < cell["chips"]:
        say(f"the cell asks for {cell['chips']} chip(s), JAX finds {len(devs)}")
        return EXIT_NO_DEVICE, None, None
    device = {"platform": platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    say(f"device {device['platform']} / {device['kind']} x {device['count']}"
        + ("  (rehearsal: tiny sizes, no device metric)" if rehearsal else ""))
    traced = bool(args.trace)
    on_chip_trace = traced and platform == "tpu"

    from windflow_tpu import native as wf_native
    from windflow_tpu.ops import resident
    from windflow_tpu.ops.backend import enable_compile_cache
    from windflow_tpu.patterns.win_seq import window_cores
    from windflow_tpu.utils import profile
    cache_dir = enable_compile_cache()
    counter = CompileCounter()
    mark("imports and device")
    if wf_native.load() is None:
        say("the checkout ships no native source")
        return EXIT_DEVICE_PATH, None, None
    mark("native library")

    config = importlib.import_module(f"configs.{cell['config']}")
    oracle = importlib.import_module(f"configs.{cell['config']}_oracle")
    chunk, rate = int(cell["chunk"]), args.rate or cell.get("rate")
    templates, id_shift, own_ts = generator.build_templates(
        oracle, cfg, args.seed, config.record_dtype(cfg), chunk)
    mark("templates")

    annotate = jax.profiler.TraceAnnotation if on_chip_trace else None
    out_dir = os.path.join(BENCH_DIR, "out", args.workload)
    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir, exist_ok=True)

    say(f"host allocator: {host_allocator.apply(cfg)}")
    # -- warm-up pass: the cell's own pipeline under its own traffic for the
    #    cell's warm-up seconds, then the coalescing ladder on the devices its
    #    executors own
    warm_gen = generator.Generator(templates, id_shift, mix, chunk, rate,
                                   float(cell["warmup"]["seconds"]),
                                   tail_seconds=0.0, own_ts=own_ts)
    warm_pipe = config.build(cfg, warm_gen, SinkRecorder(), name="warmup")
    warm_pipe.run_and_wait_end()
    n_workers = config.window_workers(cfg)

    def device_path(pipe):
        return device_assert.assert_device_path(
            window_cores(pipe._df), cfg["expected_core"], n_workers, platform)

    try:
        warm_devices, _ = device_path(warm_pipe)
    except device_assert.DevicePathError as e:
        say(f"device path (warm-up pass): {e}")
        return EXIT_DEVICE_PATH, None, None
    mark("warm-up pass")
    n_ladder = resident.prewarm_regular_ladder(devices=sorted(
        warm_devices, key=lambda d: d.id))
    del warm_pipe
    mark("ladder")
    built_in_setup = counter.compiled()

    # -- the measured pipeline
    gen = generator.Generator(templates, id_shift, mix, chunk, rate,
                              args.seconds, annotate=annotate, own_ts=own_ts)
    sink = SinkRecorder(annotate=annotate)
    node_dir = os.path.join(out_dir, "nodes") if traced else None
    pipe = config.build(cfg, gen, sink, trace_dir=node_dir)
    if traced:
        profile.enable()
    profile.reset()
    resident.stats_snapshot(reset=True)
    window = {}

    def on_start(_t0_ns):
        window["requests0"], window["hits0"] = counter.snapshot()
        window["setup_seconds"] = _AGE0 + (time.perf_counter() - _CLOCK0)

    def on_window_end():
        window["requests1"], window["hits1"] = counter.snapshot()
        window["spans"] = profile.report()
        window["resident"] = resident.stats_snapshot()
        window["steps"] = _step_shapes(resident)

    gen.on_start = on_start
    gen.on_window_end = on_window_end
    steps_before = _step_shapes(resident)
    pipe.run()
    slice_counters, trace_dir = {}, None
    if on_chip_trace:
        trace_dir = os.path.join(out_dir, "trace")
        slice_counters = trace_slice(jax, profile, gen, mix["trace_slice"],
                                     args.seconds, trace_dir)
    pipe.wait()
    t_done_ns = time.monotonic_ns()
    spans, res_stats = window["spans"], window["resident"]
    profile.auto()

    # -- the clock has stopped: device path, metrics, correctness
    log = gen.log
    try:
        _devices, dispatches = device_path(pipe)
    except device_assert.DevicePathError as e:
        say(f"device path: {e}")
        return EXIT_DEVICE_PATH, None, None
    if res_stats.get("dispatches", 0) <= 0:
        say("device path: no resident dispatch recorded in the window")
        return EXIT_DEVICE_PATH, None, None
    say(f"window cores "
        f"{device_assert.describe(cfg['expected_core'], n_workers)}, "
        f"{dispatches} dispatches on {sorted(str(d) for d in _devices)}")

    t0 = log.t0_ns
    rows = (np.concatenate(sink.rows) if sink.rows
            else np.zeros(0, dtype=config.record_dtype(cfg)))
    arrival_us = (np.repeat(np.asarray(sink.t_ns, dtype=np.int64) - t0,
                            [len(r) for r in sink.rows]) / 1e3
                  if sink.rows else np.zeros(0))
    got = {k: np.asarray(v, dtype=np.int64)
           for k, v in config.result_table(rows).items()}
    t_chk = time.perf_counter()
    want = oracle.expected(cfg, args.seed, log.for_oracle())
    numbers, (rows_g, rows_w, missing_w) = check.compare(got, want)
    correct, check_lines = check.verdict(numbers)
    for line in check_lines:
        say(line)
    say(f"checked {len(want['key'])} reference results against "
        f"{len(got['key'])} delivered in {time.perf_counter() - t_chk:.2f} s")

    # the results that are due while the stream runs on: those whose window
    # an event of the measured window closed.  The rest (windows still open
    # when the window closed) come with the tail or the end-of-stream flush.
    due_w = want["_closes_at_us"] <= log.window_last_event_us()
    due_rows = got["_row"][rows_g[due_w[rows_w]]]
    arrival_due = arrival_us[due_rows]
    latency_ms = (arrival_due
                  - config.result_event_time_us(rows)[due_rows]) / 1e3
    gen_span_ns = log.t_window_end_ns - t0
    t_last_due_ns = int(arrival_due.max() * 1e3) if len(arrival_due) \
        else gen_span_ns
    attempted = log.window_chunks * chunk
    # an event handed over late is late, not failed: its wait is in the
    # latency, which counts from its due time.  Failed are the events the
    # generator gave up on and those whose results never arrived.
    failed = (log.window_chunks - log.pushed) * chunk \
        + oracle.events_of_missing(cfg, int(np.count_nonzero(missing_w)))

    trace = None
    if trace_dir is not None:
        trace = trace_reduce.reduce_file(trace_reduce.find_xplane(trace_dir))
        if not trace["n_devices"] or trace["busy_s"] <= 0:
            say("device path: the trace shows no operation on the device")
            return EXIT_DEVICE_PATH, None, None
    nodes = []
    if node_dir and os.path.isdir(node_dir):
        for fn in sorted(os.listdir(node_dir)):
            if fn.endswith(".log"):
                with open(os.path.join(node_dir, fn)) as f:
                    nodes.append(json.load(f))
    obs = {
        "cell": cell, "cfg": cfg, "mix": mix,
        "setup_seconds": window["setup_seconds"],
        "events_in": log.handed_over * chunk,
        "window_s": max(gen_span_ns, t_last_due_ns) / 1e9,
        "latency_ms": latency_ms,
        "gen": {"busy_s": log.busy_ns / 1e9, "blocked_s": log.blocked_ns / 1e9,
                "ran_s": gen_span_ns / 1e9,
                "late_ms": (np.asarray(log.late_us[:log.window_chunks]) / 1e3
                            if log.late_us else None)},
        "nodes": nodes,
        "profile_spans": spans,
        "slice_counters": slice_counters,
        "resident": res_stats,
        "trace": trace,
        "compile_requests": window["requests1"] - window["requests0"],
        "compile_hits": window["hits1"] - window["hits0"],
        "window_workers": n_workers,
        "n_stats": int(cfg["shapes"].get("result_stats", 1)),
        "peaks": peaks.peaks_for(device["kind"]) if platform == "tpu" else None,
    }
    say(f"window: {obs['events_in']} events in {log.handed_over} chunks taken "
        f"in over {gen_span_ns / 1e9:.3f} s; last of {len(due_rows)} due "
        f"results at {t_last_due_ns / 1e9:.3f} s; then {log.n_chunks - log.window_chunks} "
        f"tail chunks, {len(rows)} results in all, graph joined at "
        f"{(t_done_ns - t0) / 1e9:.3f} s")
    if log.pushed != log.handed_over or log.pushed != log.window_chunks:
        say(f"generator: {log.pushed - log.handed_over} of the window's "
            f"{log.window_chunks} chunks handed over after its close, "
            f"{log.window_chunks - log.pushed} never (given up)")
    say(f"generator: busy {obs['gen']['busy_s']:.3f} s, in push "
        f"{obs['gen']['blocked_s']:.3f} s"
        + (f", lateness p50/p95/max {np.percentile(log.late_us, 50) / 1e3:.3f}"
           f"/{np.percentile(log.late_us, 95) / 1e3:.3f}"
           f"/{max(log.late_us) / 1e3:.3f} ms" if log.late_us else ""))
    if log.late_us:
        late = np.asarray(log.late_us)
        worst = int(late.argmax())
        say(f"latest push: chunk {worst} of {len(late)}, due at "
            f"{(worst + 1) * chunk / rate:.3f} s, {late[worst] / 1e3:.3f} ms "
            f"late; {int((late > 1e5).sum())} chunks over 100 ms late")
    if log.handed_over >= 5:
        # how steady the intake was inside the window: events/s per fifth
        edges = np.searchsorted(
            np.asarray(log.base_us[:log.handed_over]),
            np.linspace(0, gen_span_ns / 1e3, 6))
        say("intake per fifth of the window: " + " ".join(
            f"{(b - a) * chunk / (gen_span_ns / 5e9):.4g}"
            for a, b in zip(edges, edges[1:])) + " events/s")
    if len(latency_ms):
        say(f"latency samples {len(latency_ms)}: p50 "
            f"{np.percentile(latency_ms, 50):.3f} p95 "
            f"{np.percentile(latency_ms, 95):.3f} p99 "
            f"{np.percentile(latency_ms, 99):.3f} max {latency_ms.max():.3f} ms"
            f" (from due or creation times; backlog where the loop is closed)")
    say(f"resident: {res_stats}")
    say(f"compiles: {built_in_setup} built in set-up ({n_ladder} ladder "
        f"steps), {obs['compile_requests']} asked for in the window of which "
        f"{obs['compile_hits']} cache hits; cache at {cache_dir}")
    for key in window["steps"] - steps_before:
        say(f"step shape first used in the window: {key}")
    prev = marks[0][1]
    for label, t in marks[1:]:
        say(f"setup {label}: {t - prev:.3f} s")
        prev = t
    say(f"setup until the window opened: {window['setup_seconds']:.3f} s")
    if traced:
        for label, (sec, calls) in spans.items():
            say(f"span {label}: {sec:.4f} s in {calls} calls")

    kind, names = (("layer_metrics", layer_names) if traced
                   else ("e2e_metrics", e2e_names))
    metrics = read_metrics(kind, names, obs, say)
    device["memory_peak_bytes"] = max(
        (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
        for d in devs[:max(cell["chips"], 1)])
    result = {"correct": bool(correct), "attempted": int(attempted),
              "failed": int(failed), "metrics": metrics, "device": device}
    if trace is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
        for name, (sec, n) in sorted(trace["executables"].items(),
                                     key=lambda kv: -kv[1][0])[:12]:
            say(f"executable {name}: {sec:.6f} s in {n} launches")
    # every number compared beside its limit, last in the line
    result["check"] = check.beside_limits(numbers)
    detail = {"cfg": cfg, "oracle": oracle, "seed": args.seed, "got": got,
              "want": want, "log": log.for_oracle(), "numbers": numbers,
              "check_lines": check_lines}
    return 0, result, detail


def main(argv=None):
    def say(text):
        print(text, flush=True)

    rc, result, detail = measure(parse_args(argv), say)
    if result is not None:
        print(json.dumps(result), flush=True)
        # ... and as the last lines of standard error
        print("\n".join(detail["check_lines"]), file=sys.stderr, flush=True)
    return rc


if __name__ == "__main__":
    sys.exit(main())
