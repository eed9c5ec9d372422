"""The ``ysb_kf_eo`` deployment on the CPU at a small size
(benchmarks/configs/ysb_kf_eo.*): the configuration's own ``build`` with its
kill by event time, driven by the benchmark's generator on a clock the test
steps, against the plain reference and the uncrashed ``ysb_kf`` run; the two
controls that have to read wrong; the runs that have to fail; and what the
recovery layer records (NodeStats fields, spans) with ``recovery=`` and
without it.
"""

import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmarks")
for _p in (ROOT, BENCH):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from configs import ysb_kf, ysb_kf_eo, ysb_kf_eo_oracle  # noqa: E402
from harness import check, generator  # noqa: E402
from test_launch_record import _node_logs  # noqa: E402

from windflow_tpu.recovery.epoch import NodeRecovery  # noqa: E402
from windflow_tpu.utils import profile  # noqa: E402

CHUNK = 10_000            # a tenth of the recurrence's period
WIN_US = 1_000_000        # the deployment's window, a tenth of its length
STEP_NS = 5_000_000       # what the stepped clock adds a read
SECONDS = 2.6             # of that clock: windows close at 1 s and 2 s
RECOVERY_FIELDS = {
    "epochs_committed", "checkpoints_skipped", "ckpt_bytes",
    "ckpt_bytes_peak", "journal_peak", "node_restarts", "replayed_batches",
    "restore_ms", "dedup_dropped_batches"}
RECOVERY_SPANS = {"checkpoint_drain", "state_export", "state_restore",
                  "journal_replay"}


def _cfg(kill=None, **recovery):
    with open(os.path.join(BENCH, "configs", "ysb_kf_eo.json")) as f:
        cfg = json.load(f)
    cfg["shapes"].update(win_us=WIN_US, slide_us=WIN_US)
    # a barrier every few chunks of the stepped stream, not every second
    cfg["recovery"].update(epoch_period=0.02, restart_backoff=0.005)
    cfg["recovery"].update(recovery)
    cfg["kill"].update(window_index=1, offset_us=500_000)
    cfg["kill"].update(kill or {})
    return cfg


class _Clock:
    def __init__(self):
        self.now = 0

    def __call__(self):
        self.now += STEP_NS
        return self.now


def _gen(cfg, seed, seconds=SECONDS):
    templates, id_shift, own_ts = generator.build_templates(
        ysb_kf_eo_oracle, cfg, seed, ysb_kf_eo.record_dtype(cfg), CHUNK)
    return generator.Generator(
        templates, id_shift, {"loop": "closed", "tail_seconds": 0.0}, CHUNK,
        None, seconds, clock_ns=_Clock(), own_ts=own_ts)


def _run(config, cfg, seed, name=None, trace_dir=None, seconds=SECONDS):
    """One pass of ``config.build`` over the stepped stream: the sink's rows
    and the generator's log."""
    gen, got = _gen(cfg, seed, seconds), []
    kw = {} if name is None else {"name": name}
    pipe = config.build(cfg, gen, lambda r: got.append(r.copy())
                        if r is not None and len(r) else None,
                        trace_dir=trace_dir, **kw)
    pipe.run_and_wait_end(timeout=300)
    rows = (np.concatenate(got) if got
            else np.zeros(0, dtype=config.record_dtype(cfg)))
    return rows, gen.log.for_oracle(), pipe


def _numbers(config, cfg, seed, rows, log):
    got = {k: np.asarray(v, dtype=np.int64)
           for k, v in config.result_table(rows).items()}
    want = ysb_kf_eo_oracle.expected(cfg, seed, log)
    numbers, _ = check.compare(got, want)
    return numbers, got, want


def _warm(cfg, seed=1):
    """The warm-up pass every measured build asks for."""
    return _run(ysb_kf_eo, cfg, seed, name="warmup", seconds=1.2)


# ------------------------------------------------------- the crash, survived


@pytest.mark.parametrize("seed", [7, 2**31 + 11])
@pytest.mark.parametrize("kill", [
    {"window_index": 0, "offset_us": 50_000},      # a window all but empty
    {"window_index": 1, "offset_us": 500_000},     # half full: the cell's
    {"window_index": 1, "offset_us": 950_000},     # all but full
    {"window_index": 2, "offset_us": 0},           # the batch that closes one
], ids=lambda k: f"w{k['window_index']}+{k['offset_us']}")
def test_crash_reads_as_the_reference_and_as_the_uncrashed_run(kill, seed):
    cfg = _cfg(kill)
    _warm(cfg)
    rows, log, _pipe = _run(ysb_kf_eo, cfg, seed)
    numbers, got, want = _numbers(ysb_kf_eo, cfg, seed, rows, log)
    assert check.verdict(numbers)[0], numbers
    assert not ysb_kf_eo_oracle.delivery_faults(numbers)
    assert len(want["key"]) == 300          # 100 campaigns x 3 windows
    # the same stream through ysb_kf, which has no recovery and no fault
    plain, plain_log, _ = _run(ysb_kf, cfg, seed)
    assert (plain_log["base_us"] == log["base_us"]).all()
    plain_got = ysb_kf.result_table(plain)
    order = np.lexsort((got["wid"], got["key"]))
    plain_order = np.lexsort((plain_got["wid"], plain_got["key"]))
    for col in ("key", "wid", "count", "lastUpdate", "revenue"):
        assert (got[col][order]
                == np.asarray(plain_got[col])[plain_order]).all(), col
    # fired once, at the first chunk at or past the kill's event time, and
    # the worker restored once
    fired_at, counters, _all = ysb_kf_eo.fault_report()
    due = ysb_kf_eo.kill_time_us(cfg, warmup=False)
    assert fired_at == int(log["base_us"][log["base_us"] >= due][0])
    assert counters["node_restarts"] == 1
    assert counters["replayed_batches"] >= 1
    assert counters["restore_ms"] > 0


def test_the_warmup_is_killed_at_the_same_fill():
    cfg = _cfg({"window_index": 2, "offset_us": 300_000})
    _warm(cfg)
    warm_at, warm, _all = ysb_kf_eo.fault_report("warmup")
    _run(ysb_kf_eo, cfg, 3)
    at, counters, _all = ysb_kf_eo.fault_report()
    chunk_us = 3 * STEP_NS // 1000
    assert warm_at // WIN_US == 0 and at // WIN_US == 2
    assert abs(warm_at % WIN_US - at % WIN_US) <= chunk_us
    assert warm["node_restarts"] == counters["node_restarts"] == 1


# ---------------------------------------------------------------- controls


@pytest.mark.parametrize("seed", [7, 2**31 + 11])
def test_int16_accumulate_reads_wrong(seed):
    cfg = _cfg()
    _warm(cfg)
    rows, log, _ = _run(ysb_kf_eo, cfg, seed)
    numbers, _got, want = _numbers(ysb_kf_eo, cfg, seed, rows, log)
    assert check.verdict(numbers)[0]
    control = ysb_kf_eo_oracle.expected(cfg, seed, log, acc_dtype=np.int16)
    control = {k: v for k, v in control.items() if not k.startswith("_")}
    c_numbers, _ = check.compare(control, want)
    assert c_numbers["wrong.revenue"] > 0 and not check.verdict(c_numbers)[0]
    assert c_numbers["wrong.count"] == c_numbers["wrong.lastUpdate"] == 0


#: the delivery control's kill: the call after the one in which window 0's
#: results left, so the replay re-emits them
BEHIND_A_CLOSE = {"window_index": 1, "offset_us": 0, "after_emit": True}


def _behind_a_close():
    """No barrier but epoch 0's, and a launch every 8,192 rows: a closed
    window's results leave with the next launch and a crash replays them."""
    cfg = _cfg(BEHIND_A_CLOSE, epoch_period=30.0)
    cfg["ship"]["flush_rows"] = 8192
    return cfg


@pytest.mark.parametrize("seed", [7, 2**31 + 11])
def test_delivery_with_the_replayed_prefix_dropped_is_exactly_once(seed):
    cfg = _behind_a_close()
    _warm(cfg)
    rows, log, _pipe = _run(ysb_kf_eo, cfg, seed)
    numbers, _got, _want = _numbers(ysb_kf_eo, cfg, seed, rows, log)
    assert check.verdict(numbers)[0], numbers
    _at, _killed, report = ysb_kf_eo.fault_report()
    assert sum(c["dedup_dropped_batches"] for c in report.values()) > 0


@pytest.mark.parametrize("seed", [7, 2**31 + 11])
def test_delivery_with_the_drop_turned_off_reads_wrong(seed, monkeypatch):
    """The comparison sees at-least-once as a failure: with the consumer's
    drop of a replayed prefix off (this test's patch: the library has no such
    option) a window reaches the sink twice."""
    cfg = _behind_a_close()
    _warm(cfg)
    monkeypatch.setattr(NodeRecovery, "is_replayed",
                        lambda self, src, seq: False)
    rows, log, _ = _run(ysb_kf_eo, cfg, seed)
    numbers, _got, _want = _numbers(ysb_kf_eo, cfg, seed, rows, log)
    assert not check.verdict(numbers)[0]
    assert set(ysb_kf_eo_oracle.delivery_faults(numbers)) == {"duplicates"}
    assert numbers["duplicates"] >= 25          # a worker's campaigns
    assert numbers["missing"] == numbers["wrong.revenue"] == 0


# ------------------------------------------------- the runs that have to fail


def test_a_kill_that_does_not_fire_fails_the_run():
    cfg = _cfg({"warmup_window_index": 40})
    _run(ysb_kf_eo, cfg, 5, name="warmup", seconds=1.2)
    with pytest.raises(RuntimeError, match="warmup pass's fault never fired"):
        ysb_kf_eo.build(cfg, _gen(cfg, 5), lambda r: None)
    cfg = _cfg({"window_index": 40, "warmup_window_index": 0,
                "offset_us": 200_000})
    _warm(cfg)
    rows, _log, _ = _run(ysb_kf_eo, cfg, 5)
    assert len(rows)
    with pytest.raises(RuntimeError, match="measured pass's fault never"):
        ysb_kf_eo.result_table(rows)


def test_a_worker_that_is_not_restored_fails_the_run():
    cfg = _cfg(max_restarts=0)
    with pytest.raises(ysb_kf_eo.InjectedCrash):
        _warm(cfg)


def test_a_measured_pass_needs_its_warmup():
    ysb_kf_eo._BUILT.clear()
    cfg = _cfg()
    with pytest.raises(RuntimeError, match="no warmup pass"):
        ysb_kf_eo.build(cfg, _gen(cfg, 5), lambda r: None)


# --------------------------------------------- what the recovery layer records


@pytest.fixture()
def profiled():
    profile.enable()
    profile.reset()
    yield
    profile.auto()
    profile.reset()


def test_fields_and_spans_under_recovery(tmp_path, profiled):
    cfg = _cfg()
    _warm(cfg)
    profile.reset()
    _run(ysb_kf_eo, cfg, 9, trace_dir=str(tmp_path))
    logs = _node_logs(str(tmp_path))
    supervised = {n: log for n, log in logs.items() if "source" not in n}
    assert len(supervised) == 7     # emitter, four workers, collector, sink
    for name, log in supervised.items():
        assert RECOVERY_FIELDS <= set(log), name
    assert not any(RECOVERY_FIELDS & set(log) for n, log in logs.items()
                   if "source" in n)
    workers = [log for n, log in supervised.items() if "ysb_kf_tpu." in n
               and n[-1].isdigit()]
    assert len(workers) == 4
    assert sorted(w["node_restarts"] for w in workers) == [0, 0, 0, 1]
    for w in workers:
        assert w["epochs_committed"] >= 1 and w["checkpoints_skipped"] == 0
        assert w["ckpt_bytes_peak"] > 0 and w["ckpt_bytes"] >= \
            w["ckpt_bytes_peak"]
        assert w["journal_peak"] >= 1
    killed = next(w for w in workers if w["node_restarts"])
    assert killed["replayed_batches"] >= 1 and killed["restore_ms"] > 0
    spans = profile.report()
    assert RECOVERY_SPANS <= set(spans)
    assert spans["state_restore"][1] == spans["journal_replay"][1] == 1
    assert spans["state_export"][1] == spans["checkpoint_drain"][1] >= 8
    with open(os.path.join(str(tmp_path), "launches.jsonl")) as f:
        phases = {json.loads(line)["phase"] for line in f}
    assert RECOVERY_SPANS <= phases


def test_no_field_and_no_span_without_recovery(tmp_path, profiled):
    cfg = _cfg()
    _run(ysb_kf, cfg, 9, trace_dir=str(tmp_path))
    logs = _node_logs(str(tmp_path))
    assert len(logs) == 8
    for name, log in logs.items():
        assert not RECOVERY_FIELDS & set(log), name
    assert not RECOVERY_SPANS & set(profile.report())


@pytest.fixture(scope="module")
def warm_report():
    _rows, _log, pipe = _warm(_cfg())
    return pipe.recovery_report()


@pytest.mark.parametrize("field", sorted(RECOVERY_FIELDS))
def test_recovery_report_names_every_field(field, warm_report):
    report = warm_report
    assert len(report) == 7 and all(field in c for c in report.values())
    assert all(isinstance(c[field], (int, float)) for c in report.values())
