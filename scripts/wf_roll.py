"""Zero-downtime rolling restart sequencer for a supervised row plane
(docs/ROBUSTNESS.md "Cross-host recovery"): cycle every stateful worker
process through drain -> seal -> hand-off -> restart while the source
keeps emitting, then verify the merged outputs are byte-identical to the
uncrashed oracle (zero record loss, zero duplication).

The four phases, per rolled worker:

  drain     the feeding MultiPipe's control-plane ``Drain`` actuator
            gates the sources and settles every inbox (quiesce), so no
            new rows are in flight anywhere in the graph
  seal      an epoch barrier is shipped on every plane edge; the worker
            checkpoints its state (CheckpointStore) and acks the sealed
            epoch, trimming the feeder's resume journal to the barrier
  hand-off  the worker exits at the seal WITHOUT an EOS — the feeder's
            journaling senders mark the link down and hold the unsealed
            tail for replay (parallel/channel.py wire resume)
  restart   a fresh process restores the sealed checkpoint and rebinds
            the same plane address with ``resume_epoch=``; the senders
            reconnect and replay exactly the records past the barrier;
            ``release_drain()`` resumes emission

Run the built-in differential (a feeder MultiPipe + 2 worker processes,
each rolled once mid-stream):

    python scripts/wf_roll.py --epochs 8 -v

The same sequence is exercised in-suite by
tests/test_multihost_2proc.py::test_rolling_restart_zero_loss.
"""

import argparse
import json
import os
import subprocess
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

#: the rolled worker: seal per-epoch running sums, exit at a seal when
#: the roll flag is present (phase A) or resume from a sealed epoch
#: (phase B) — the wf_roll sequencer drives both phases
_WORKER = r"""
import json, os, sys
from windflow_tpu.parallel.channel import RowReceiver, WireResume
from windflow_tpu.recovery.epoch import EpochMarker
from windflow_tpu.recovery.store import CheckpointStore

w = int(sys.argv[1])
port, root, flag = int(sys.argv[2]), sys.argv[3], sys.argv[4]
resume_from = int(sys.argv[5])

store = CheckpointStore(os.path.join(root, f"store{w}"), retain=8)
sums = {}
if resume_from:
    latest = store.latest_complete()
    assert latest is not None and latest[0] == resume_from, latest
    sums = store.load(resume_from, "sums")

recv = RowReceiver(1, port=port, resume=WireResume(deadline=120.0),
                   resume_epoch=(resume_from or None), ack_epochs=False,
                   accept_timeout=60.0)
pending = []
out_f = open(os.path.join(root, f"out{w}.jsonl"), "a")
for item in recv.batches(epoch_markers=True):
    if isinstance(item, EpochMarker):
        e = int(item.epoch)
        n = store.save_blob(e, "sums", dict(sums))
        store.commit(e, {"sums": {"bytes": n}})
        for row in pending:
            out_f.write(json.dumps(row) + "\n")
        out_f.flush()
        os.fsync(out_f.fileno())
        pending = []
        recv.ack_epoch(e)
        if os.path.exists(flag):
            os._exit(0)   # hand-off: exit at the seal, no EOS — the
            #               feeder's journal bridges the restart gap
        continue
    for r in item:
        k, v = int(r["key"]), int(r["value"])
        sums[k] = sums.get(k, 0) + v
        pending.append([k, int(r["id"]), sums[k]])
recv.close()
"""


def _spawn_worker(w, port, root, flag, resume_from, script, env):
    return subprocess.Popen(
        [sys.executable, script, str(w), str(port), root, flag,
         str(resume_from)],
        env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE)


def roll_worker(pipe, w, port, workers, senders, state, root, flag,
                script, env, verbose=False):
    """One drain -> seal -> hand-off -> restart cycle for worker ``w``;
    returns the epoch the restarted process resumed from."""
    from windflow_tpu.recovery.store import CheckpointStore

    if not pipe.request_drain(timeout=60.0):
        raise RuntimeError(f"drain for worker {w} never quiesced")
    # the flag goes down only AFTER quiesce: from here the one marker
    # the worker will see is the sequencer's own seal below, so it
    # exits exactly at the drained barrier
    with open(flag, "w"):
        pass
    # seal: every plane edge gets a barrier at the drained point (the
    # current epoch may be mid-stream — an extra marker is just a finer
    # seal, the per-key stream content is unchanged)
    state["epoch"] += 1
    for snd in senders.values():
        snd.send_epoch(state["epoch"])
    _out, err = workers[w].communicate(timeout=120)
    if workers[w].returncode != 0:
        raise RuntimeError(f"worker {w} failed at hand-off: "
                           f"{err.decode()[-2000:]}")
    sealed = CheckpointStore(os.path.join(root, f"store{w}"),
                             retain=8).latest_complete()
    if sealed is None:
        raise RuntimeError(f"worker {w} left no complete checkpoint")
    os.unlink(flag)
    workers[w] = _spawn_worker(w, port, root, flag, sealed[0], script, env)
    pipe.release_drain()
    if verbose:
        print(f"rolled worker {w}: sealed epoch {sealed[0]}, "
              f"restarted with resume_epoch={sealed[0]}")
    return sealed[0]


def run_roll(root, n_epochs=8, verbose=False):
    """The built-in differential: a Drain-controlled feeder MultiPipe
    ships a deterministic keyed stream to 2 worker processes; each is
    rolled once mid-stream; merged outputs must equal the uncrashed
    oracle."""
    from windflow_tpu.api import MultiPipe
    from windflow_tpu.control import ControlPolicy, Drain
    from windflow_tpu.core.tuples import Schema, batch_from_columns
    from windflow_tpu.parallel.channel import RowSender, WireResume
    from windflow_tpu.patterns.basic import Sink, Source

    script = os.path.join(root, "roll_worker.py")
    with open(script, "w") as f:
        f.write(_WORKER)
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))) + os.pathsep + env.get("PYTHONPATH", ""))
    # the workers run host-only graphs: pinned off the accelerator, so no
    # child ever contends for a chip its parent (or a sibling) may hold
    env["JAX_PLATFORMS"] = "cpu"

    import socket
    ports = {}
    for w in (1, 2):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        ports[w] = s.getsockname()[1]
        s.close()
    flags = {w: os.path.join(root, f"roll{w}.flag") for w in (1, 2)}
    workers = {w: _spawn_worker(w, ports[w], root, flags[w], 0, script,
                                env)
               for w in (1, 2)}
    senders = {w: RowSender("127.0.0.1", ports[w],
                            resume=WireResume(deadline=120.0),
                            connect_deadline=60.0)
               for w in (1, 2)}

    schema = Schema(value=np.int64)
    state = {"bi": 0, "epoch": 0}

    def gen():
        for bi in range(2 * n_epochs):
            keys = np.arange(8, dtype=np.int64)
            ids = np.full(8, bi, dtype=np.int64)
            yield batch_from_columns(schema, key=keys, id=ids, ts=ids,
                                     value=7 * ids + keys + 1)
            time.sleep(0.02)   # the source keeps emitting through rolls

    def ship(rows):
        if rows is None:
            return
        keys = np.asarray(rows["key"])
        for w, snd in senders.items():
            m = (1 + keys % 2) == w
            if m.any():
                snd.send(rows[m])
        state["bi"] += 1
        if state["bi"] % 2 == 0:
            state["epoch"] += 1
            for snd in senders.values():
                snd.send_epoch(state["epoch"])

    pipe = (MultiPipe("wf_roll_feeder", capacity=8, metrics=True,
                      control=ControlPolicy([Drain(deadline=60.0,
                                                   poll=0.01)],
                                            period=0.05)))
    pipe.add_source(Source(batches=gen(), schema=schema, name="src"))
    pipe.add_sink(Sink(ship, vectorized=True, name="ship"))
    pipe.run()
    time.sleep(0.3)   # rows flowing before the first roll
    for w in sorted(workers):
        roll_worker(pipe, w, ports[w], workers, senders, state, root,
                    flags[w], script, env, verbose=verbose)
        time.sleep(0.2)
    pipe.wait(timeout=120)
    for snd in senders.values():
        snd.close()
    for w, p in workers.items():
        _out, err = p.communicate(timeout=120)
        if p.returncode != 0:
            raise RuntimeError(f"worker {w} failed after roll: "
                               f"{err.decode()[-2000:]}")

    # uncrashed oracle: per-key running sums over the generated stream
    want, sums = {}, {}
    for bi in range(2 * n_epochs):
        for k in range(8):
            v = 7 * bi + k + 1
            sums[k] = sums.get(k, 0) + v
            want.setdefault(k, []).append([bi, sums[k]])
    got = {}
    for w in (1, 2):
        with open(os.path.join(root, f"out{w}.jsonl")) as f:
            for line in f:
                k, rid, cum = json.loads(line)
                got.setdefault(int(k), []).append([int(rid), int(cum)])
    for rows in got.values():
        rows.sort()
    assert got == want, "rolled outputs diverged from the oracle"
    snap = pipe.metrics.snapshot()
    return {"rolled": sorted(workers),
            "drains": snap["counters"].get("ctl_drains", 0),
            "epochs_sealed": state["epoch"]}


def wf_check_pipelines():
    """Static-analysis entry (scripts/wf_lint.py, docs/CHECKS.md): a
    tiny never-run instance of the feeder MultiPipe the roll sequencer
    drives (Drain-controlled source -> shipping sink), with a trace_dir
    so the metrics knob validates clean."""
    import tempfile

    from windflow_tpu.api import MultiPipe
    from windflow_tpu.control import ControlPolicy, Drain
    from windflow_tpu.core.tuples import Schema
    from windflow_tpu.patterns.basic import Sink, Source

    schema = Schema(value=np.int64)
    pipe = MultiPipe("wf_roll_feeder", capacity=8, metrics=True,
                     trace_dir=tempfile.gettempdir(),
                     control=ControlPolicy([Drain(deadline=60.0,
                                                  poll=0.01)],
                                           period=0.05))
    pipe.add_source(Source(batches=[], schema=schema, name="src"))
    pipe.add_sink(Sink(lambda rows: None, vectorized=True, name="ship"))
    return [pipe]


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--epochs", type=int, default=8)
    ap.add_argument("-v", "--verbose", action="store_true")
    args = ap.parse_args()
    import tempfile
    with tempfile.TemporaryDirectory(prefix="wf_roll_") as root:
        out = run_roll(root, n_epochs=args.epochs, verbose=args.verbose)
    print(f"rolling restart OK: workers {out['rolled']} cycled with "
          f"{out['drains']} drains over {out['epochs_sealed']} sealed "
          f"epochs, outputs byte-identical to the uncrashed oracle")


if __name__ == "__main__":
    main()
