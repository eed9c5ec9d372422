"""Shared validator for the observability file schemas
(docs/OBSERVABILITY.md): every line of ``metrics.jsonl`` and
``events.jsonl`` must parse and carry the documented fields with the
documented types.  One definition, imported by the tier-1 smoke test and
the slow soak slice — the schema the docs promise is the schema the
tests enforce."""

import json

from windflow_tpu.obs.events import EVENT_KINDS

#: required metrics-sample fields -> accepted types
SAMPLE_FIELDS = {
    "t": (float,),
    "seq": (int,),
    "dataflow": (str,),
    "nodes": (list,),
    "dead_letters": (int,),
    "counters": (dict,),
    "gauges": (dict,),
    "histograms": (dict,),
}

#: required per-node fields (sampler may add optional NodeStats fields:
#: rcv_batches, rcv_tuples, ewma/avg_service_us_per_batch)
NODE_FIELDS = {
    "node": (str,),
    "id": (str,),
    "depth": (int,),
    "hwm": (int,),
    "shed": (int,),
    "quarantined": (int,),
}

NODE_OPTIONAL_FIELDS = {
    "rcv_batches": (int,),
    "rcv_tuples": (int,),
    "ewma_service_us_per_batch": (int, float),
    "avg_service_us_per_batch": (int, float),
    # the gather's counters (Filter stages, keyed StandardEmitter)
    "filter_rows_in": (int,),
    "filter_rows_out": (int,),
    "split_batches": (int,),
    "single_dest_batches": (int,),
    # the Filter's hand-on (core/tuples.Selection): batches it handed on
    # ungathered, ÷ its emitting batches; on the keyed emitter the batches
    # and rows that came so, ÷ rcv_batches / rcv_tuples.  A selection's
    # len() is its survivor count: what rcv_tuples and a hop's rows read
    "filter_selections": (int,),
    "selection_batches": (int,),
    "selection_rows": (int,),
    # a stream-time host window worker (core/vecinc.VecStreamCore): the
    # chunks its native fold took, ÷ (non_triggering_batches +
    # triggering_batches) its engagement; the most keys it held at once
    "fold_native_batches": (int,),
    "keys_live_peak": (int,),
    # a supervised node under recovery= (recovery/epoch.NodeRecovery
    # .counters, copied onto its NodeStats as it ends)
    "epochs_committed": (int,),
    "checkpoints_skipped": (int,),
    "ckpt_bytes": (int,),
    "ckpt_bytes_peak": (int,),
    "journal_peak": (int,),
    "node_restarts": (int,),
    "replayed_batches": (int,),
    "restore_ms": (int, float),
    "dedup_dropped_batches": (int,),
    # span-tracing latency fields (obs/trace.py; only on traced graphs)
    "q_p50_us": (int, float),
    "q_p95_us": (int, float),
    "q_p99_us": (int, float),
    "svc_p50_us": (int, float),
    "svc_p95_us": (int, float),
    "svc_p99_us": (int, float),
}

#: span-record kinds (trace.jsonl, obs/trace.py) -> kind-specific
#: required fields; the common fields are checked for every kind
SPAN_COMMON_FIELDS = {
    "t": (float,),
    "kind": (str,),
    "span": (int,),
    "dataflow": (str,),
}
SPAN_KIND_FIELDS = {
    "hop": {"trace": (int,), "node": (str,), "q_us": (int, float),
            "svc_us": (int, float), "end_us": (int, float),
            "rows": (int,)},
    "launch": {"trace": (int,), "phase": (str,), "dur_us": (int, float),
               "end_us": (int, float)},
    "ctrl": {"name": (str,), "node": (str,), "epoch": (int,),
             "dur_us": (int, float)},
}


def _typed(obj, field, types, ctx):
    assert field in obj, f"{ctx}: missing field {field!r} in {obj}"
    v = obj[field]
    assert isinstance(v, types) and not (
        bool not in types and isinstance(v, bool)), \
        f"{ctx}: field {field!r} has type {type(v).__name__}, " \
        f"wanted {[t.__name__ for t in types]}"
    return v


def validate_sample(sample: dict, ctx: str = "metrics.jsonl"):
    """One metrics.jsonl record against the documented schema."""
    for field, types in SAMPLE_FIELDS.items():
        _typed(sample, field, types, ctx)
    assert sample["seq"] >= 0, f"{ctx}: negative seq"
    assert sample["t"] > 0, f"{ctx}: non-positive timestamp"
    for node in sample["nodes"]:
        nctx = f"{ctx} node {node.get('node')!r}"
        for field, types in NODE_FIELDS.items():
            v = _typed(node, field, types, nctx)
            if field in ("depth", "hwm", "shed", "quarantined"):
                assert v >= 0, f"{nctx}: negative {field}"
        for field, types in NODE_OPTIONAL_FIELDS.items():
            if field in node:
                _typed(node, field, types, nctx)
    for name, v in sample["counters"].items():
        assert isinstance(v, (int, float)), \
            f"{ctx}: counter {name!r} not numeric"
    for name, h in sample["histograms"].items():
        for field in ("buckets", "sum", "count"):
            assert field in h, f"{ctx}: histogram {name!r} missing {field}"


def validate_span(rec: dict, ctx: str = "trace.jsonl"):
    """One trace.jsonl span record against the documented schema
    (docs/OBSERVABILITY.md §tracing)."""
    for field, types in SPAN_COMMON_FIELDS.items():
        _typed(rec, field, types, ctx)
    kind = rec["kind"]
    assert kind in SPAN_KIND_FIELDS, f"{ctx}: unknown span kind {kind!r}"
    for field, types in SPAN_KIND_FIELDS[kind].items():
        v = _typed(rec, field, types, ctx)
        if field in ("q_us", "svc_us", "dur_us", "rows"):
            assert v >= 0, f"{ctx}: negative {field}"
    # parent is optional-by-None: root hops and ctrl spans carry None
    if rec.get("parent") is not None:
        _typed(rec, "parent", (int,), ctx)
    json.dumps(rec)     # every field must be JSON-serialisable


def validate_event(event: dict, ctx: str = "events.jsonl"):
    """One events.jsonl record against the documented schema."""
    _typed(event, "t", (float,), ctx)
    kind = _typed(event, "event", (str,), ctx)
    assert kind in EVENT_KINDS, f"{ctx}: unknown event kind {kind!r}"
    if "node" in event:
        _typed(event, "node", (str,), ctx)
    json.dumps(event)   # every field must be JSON-serialisable


def validate_file(path: str, validator) -> int:
    """Validate every line of a JSONL file; returns the line count (a
    caller asserting `> 0` distinguishes 'valid' from 'empty')."""
    n = 0
    with open(path) as f:
        for i, line in enumerate(f, 1):
            ctx = f"{path}:{i}"
            assert line.endswith("\n"), f"{ctx}: torn/unterminated line"
            try:
                obj = json.loads(line)
            except json.JSONDecodeError as e:
                raise AssertionError(f"{ctx}: invalid JSON: {e}") from e
            validator(obj, ctx)
            n += 1
    return n
