"""Multi-host execution: process-spanning meshes with DCN-aware layout.

The reference is strictly single-process — "communication" is FastFlow
shared-memory queues between pinned threads (SURVEY.md §2.8: no NCCL, no
MPI, no sockets).  The TPU-native scale-out story goes further: a
``jax.distributed``-initialised job sees every host's chips as one device
set, and the streaming mesh axes (kf × wf × sp, parallel/mesh.py) extend
across hosts with the axis→network mapping chosen so that

* ``kf`` (key groups — Key_Farm parallelism) is split OVER HOSTS first:
  key groups exchange nothing, so the slow inter-host DCN hops carry no
  collective traffic at all;
* ``sp`` (within-window partition — the psum/ring-ppermute axis) stays
  INSIDE one host's slice, so its collectives ride ICI.

This is the streaming analog of the scaling-book recipe "data-parallel
over DCN, model-parallel over ICI".

Deployment model: one engine process per host.  Host-side dataflow
(sources, emitters, host operators) runs per process over its own keys —
``process_for_keys`` gives the owner of each key, and a source that
generates (or receives) only its own key range needs no cross-host hop at
all, exactly like the reference's per-worker key partitioning
(kf_nodes.hpp routing) lifted one level.  Device-side, the sharded
executors (a resident executor placed on a mesh, ``MeshStreamStep``) run
one SPMD program over the global mesh; XLA inserts the (absent, for kf)
DCN collectives.
"""

from __future__ import annotations

import numpy as np

import jax
from jax.sharding import Mesh

from .mesh import KF_AXIS, SP_AXIS, WF_AXIS


def initialize(coordinator_address=None, num_processes=None,
               process_id=None, **kw):
    """``jax.distributed.initialize`` pass-through.  A zero-arg call
    DELEGATES to jax's cluster auto-detection (the canonical spelling on
    a real multi-host TPU pod — swallowing it here would silently build
    single-host meshes with wrong kf ownership).  The only no-op is the
    EXPLICIT single-process job, ``num_processes=1`` with no coordinator:
    there is nothing to coordinate."""
    if (num_processes == 1 and coordinator_address is None
            and process_id in (None, 0) and not kw):
        return
    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes, process_id=process_id, **kw)


def _group_by_process(devices, process_of=None):
    """Devices grouped by owning process, process ids ascending.
    ``process_of`` overrides the grouping (tests simulate multi-host on
    virtual single-process devices by injecting a mapping)."""
    pid = (process_of if process_of is not None
           else (lambda d: d.process_index))
    groups = {}
    for d in devices:
        groups.setdefault(pid(d), []).append(d)
    return [groups[p] for p in sorted(groups)]


def make_multihost_mesh(n_kf=None, n_sp: int = 1, n_wf: int = 1,
                        devices=None, process_of=None) -> Mesh:
    """A (kf, wf, sp) mesh over every process's devices with ``kf``
    outermost ALONG THE PROCESS BOUNDARY: the first ``n_processes``
    divisions of the kf axis are whole hosts, so no kf index spans two
    hosts and every sp/wf neighbour lives on the same host (collectives
    on ICI, nothing on DCN).

    ``n_kf`` defaults to all remaining parallelism
    (n_devices // (n_sp * n_wf)); passing it explicitly is validation
    only — it must equal exactly ``n_hosts * per_host_share`` (this mesh
    always spans every device; carve a subset with ``devices=``).
    Constraint: ``n_sp * n_wf`` must divide each host's device count.
    """
    devices = list(devices if devices is not None else jax.devices())
    per_proc = _group_by_process(devices, process_of)
    n_local = len(per_proc[0])
    if any(len(g) != n_local for g in per_proc):
        raise ValueError(
            f"hosts disagree on device count: {[len(g) for g in per_proc]}")
    inner = n_sp * n_wf
    if n_local % inner:
        raise ValueError(
            f"sp*wf = {inner} must divide the per-host device count "
            f"{n_local} (sp collectives must stay on one host's ICI)")
    kf_per_proc = n_local // inner
    total_kf = kf_per_proc * len(per_proc)
    if n_kf is None:
        n_kf = total_kf
    if n_kf != total_kf:
        raise ValueError(
            f"n_kf={n_kf} but the ({len(per_proc)} hosts x {n_local} "
            f"devices) / (sp*wf={inner}) layout gives kf={total_kf}")
    # grid[kf, wf, sp]: host-major kf, then each host's devices reshaped
    # into its local (kf_per_proc, wf, sp) block
    blocks = [np.asarray(g, dtype=object).reshape(kf_per_proc, n_wf, n_sp)
              for g in per_proc]
    grid = np.concatenate(blocks, axis=0)
    return Mesh(grid, (KF_AXIS, WF_AXIS, SP_AXIS))


def process_for_keys(keys: np.ndarray, mesh: Mesh, process_of=None,
                     routing=None) -> np.ndarray:
    """Owning process id per key: key -> kf group -> the process whose
    devices hold that kf row.  A multi-host source keeps only
    ``process_for_keys(k, mesh) == my_pid`` and never ships rows over
    DCN.  ``routing(keys, n_kf) -> groups`` must be the SAME function the
    deployment's emitters use (default: key % n, default_routing) — a
    mismatch would place rows on hosts that don't own their kf group."""
    n_kf = int(mesh.shape[KF_AXIS])
    if routing is None:
        from ..runtime.emitters import default_routing as routing
    pid = (process_of if process_of is not None
           else (lambda d: d.process_index))
    kf_owner = np.asarray(
        [pid(mesh.devices[g, 0, 0]) for g in range(n_kf)])
    return kf_owner[np.asarray(
        routing(np.asarray(keys, dtype=np.int64), n_kf), dtype=np.int64)]


def open_row_plane(my_pid: int, addresses: dict, capacity: int = 64,
                   wire=None, metrics=None, events=None,
                   decode_trace: bool = False, resume=None,
                   resume_epoch: int = None, ckpt_sink=None,
                   telemetry_sink=None):
    """Build the full cross-host row data plane for a process: one
    :class:`~windflow_tpu.parallel.channel.RowReceiver` listening at
    ``addresses[my_pid]`` and one hardened
    :class:`~windflow_tpu.parallel.channel.RowSender` per remote process,
    returned as ``(receiver, {pid: sender})`` — the handles
    ``partition_and_ship`` wants.

    ``addresses`` maps process id -> ``(host, port)`` for every process
    in the job (the deployment's static wiring, typically derived from
    the coordinator address + a port base).  ``wire`` is a
    :class:`~windflow_tpu.parallel.channel.WireConfig`; the default is
    ``WireConfig.hardened()`` — unlike the raw channel classes (whose
    bare defaults stay seed-identical), a *plane* built through this
    helper gets retries, heartbeats and stall timeouts out of the box,
    because hosts boot in arbitrary order and a production job must
    degrade loudly, not hang, when a peer dies (docs/ROBUSTNESS.md).
    Connect order is safe in any boot order: the receiver is bound
    before any outbound connect, and connects retry with backoff until
    the wire deadline.

    ``metrics`` (an ``obs.MetricsRegistry``) and ``events`` (an
    ``obs.EventLog``) opt the whole plane into wire telemetry: every
    channel of this process shares the one registry, so
    ``wire_bytes_sent`` / ``wire_connect_retries`` / heartbeat counters
    aggregate across peers, and reconnect/stall/abort events carry per
    -peer detail (docs/OBSERVABILITY.md).  Pass the owning Dataflow's
    ``.metrics`` / ``.events`` to fold the wire into its sampler
    output; both None (default) = no telemetry, seed-identical wire.

    ``decode_trace=True`` re-attaches inbound span-trace frames
    (``send(..., trace=obs.trace.export())`` on the peer) to their
    batches as ``TracedRows`` so a traced source on this host adopts
    them and the multihost graph stitches one trace
    (docs/OBSERVABILITY.md §tracing); the default discards them.

    ``resume`` (``True`` or a tuned
    :class:`~windflow_tpu.parallel.channel.WireResume`; default taken
    from ``wire.resume``) makes every edge of this plane *resumable*
    (docs/ROBUSTNESS.md "Wire resume"): senders journal outbound frames
    and replay the unacked tail over a fresh connection when a peer
    restarts, receivers dedup by seq — so peer death inside the resume
    deadline becomes a bounded retry instead of a graph error.  A
    RESTARTED process reopening its half of the plane passes
    ``resume_epoch=K`` (its last sealed checkpoint epoch): its receiver
    then asks each reconnecting sender to replay from the epoch-``K``
    barrier rather than from a seq it no longer remembers, which is
    exactly the wire tail the restored dataflow needs.  Unset (and
    unset on ``wire``) ⇒ the plane behaves byte-identically to before
    (no journal, no handshake).

    ``ckpt_sink`` (typically a ``recovery.portable.PortableSpool``)
    opts this process into RECEIVING peers' portable checkpoints (the
    ``-7`` wire family): each peer's sealed epochs land under the
    spool, which is what a :class:`~windflow_tpu.parallel.plane.
    PlaneSupervisor` successor restores a dead peer from
    (docs/ROBUSTNESS.md "Cross-host recovery").  Unset ⇒ the family is
    refused on arrival and nothing new is imported — the seed
    contract.

    ``telemetry_sink`` (typically an ``obs.federation.
    TelemetryAggregator``) opts this process into RECEIVING peers'
    federated-telemetry snapshots (the ``-8`` wire family,
    docs/OBSERVABILITY.md "Federation & SLOs").  Same contract as
    ``ckpt_sink``: unset ⇒ the family is refused on arrival and nothing
    new is imported."""
    from .channel import RowReceiver, RowSender, WireConfig
    if my_pid not in addresses:
        raise KeyError(f"addresses has no entry for this process "
                       f"(pid {my_pid}): {sorted(addresses)}")
    if wire is None:
        wire = WireConfig.hardened()
    wire.validate()   # reject heartbeat >= stall_timeout (WF205)
    host, port = addresses[my_pid]
    receiver = RowReceiver(n_senders=len(addresses) - 1, host=host,
                           port=port, capacity=capacity,
                           # wire= supplies stall_timeout and the
                           # accept deadline (a peer that dies before
                           # ever connecting must surface within the
                           # boot-order budget, not hang batches())
                           metrics=metrics, events=events,
                           decode_trace=decode_trace,
                           resume=resume, resume_epoch=resume_epoch,
                           ckpt_sink=ckpt_sink,
                           telemetry_sink=telemetry_sink, wire=wire)
    senders = {}
    try:
        for pid in sorted(addresses):
            if pid == my_pid:
                continue
            peer_host, peer_port = addresses[pid]
            senders[pid] = RowSender(
                peer_host, peer_port,
                metrics=metrics, events=events,
                resume=resume, wire=wire)
    except Exception:
        for snd in senders.values():
            snd.abort()
        receiver.close()
        raise
    return receiver, senders


def ship_epoch(senders: dict, epoch: int, my_pid: int = None):
    """Broadcast an epoch barrier frame on every outbound row channel of
    this process's data plane (the multihost half of the recovery
    layer's epoch alignment, docs/ROBUSTNESS.md "Recovery"): a source
    that injects epoch ``e`` locally calls this so remote consumers'
    ``batches(epoch_markers=True)`` aligns on the same boundary.  Call
    it AFTER the epoch's last ``partition_and_ship`` — the frame
    promises every row of epochs <= ``e`` is already on the wire.

    On a resumable plane (``open_row_plane(resume=...)``) the epoch
    frame is also the journal's unit of truncation: once the remote
    receiver acks epoch ``e`` (automatic under ``WireConfig(recovery=
    True)``), every journaled frame up to and including this barrier is
    dropped — so calling ``ship_epoch`` at your checkpoint cadence is
    what keeps sender journals bounded by one epoch's width."""
    for pid, snd in senders.items():
        if my_pid is not None and pid == my_pid:
            continue
        snd.send_epoch(epoch)


def local_kf_groups(mesh: Mesh, process_index=None,
                    process_of=None) -> np.ndarray:
    """The kf-group indices whose device rows live on this process."""
    if process_index is None:
        process_index = jax.process_index()
    n_kf = int(mesh.shape[KF_AXIS])
    pid = (process_of if process_of is not None
           else (lambda d: d.process_index))
    return np.asarray([g for g in range(n_kf)
                       if pid(mesh.devices[g, 0, 0]) == process_index])
