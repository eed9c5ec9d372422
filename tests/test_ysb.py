"""Yahoo Streaming Benchmark correctness tests (yahoo_test_cpu analog):
deterministic event batches through the full YSB pipeline, per-window
per-campaign counts checked against a numpy oracle, kf vs wmr differential."""

import threading

import numpy as np
import pytest

from windflow_tpu.apps.ysb import (ADS_PER_CAMPAIGN, EVENT_SCHEMA,
                                   N_CAMPAIGNS, CampaignGenerator,
                                   YSBAggregate, build_pipeline)
from windflow_tpu.core.tuples import batch_from_columns

WIN_SEC = 0.01          # 10 ms tumbling windows (10s scaled down)
WIN_US = int(WIN_SEC * 1e6)


def fixed_batches(n_events, chunk=1000, ts_step_us=50):
    """Deterministic event stream: the reference's ad/event recurrences with
    a linear timestamp ramp (ts_step_us per event)."""
    campaigns = CampaignGenerator()
    out = []
    for lo in range(0, n_events, chunk):
        v = np.arange(lo, min(lo + chunk, n_events), dtype=np.int64)
        vm = v % 100000
        out.append(batch_from_columns(
            EVENT_SCHEMA, key=np.zeros(len(v), dtype=np.int64), id=v,
            ts=v * ts_step_us, ad_id=vm % campaigns.n_ads,
            event_type=(vm % 3).astype(np.int8),
            revenue=(vm % 97) + 1))
    return out


def oracle_counts(n_events, ts_step_us=50, win_us=WIN_US):
    """Expected {(cmp_id, window_index): count} over filtered events."""
    campaigns = CampaignGenerator()
    v = np.arange(n_events, dtype=np.int64)
    vm = v % 100000
    keep = vm % 3 == 0
    cmp_ids = campaigns.ad_to_cmp[(vm % campaigns.n_ads)[keep]]
    wins = (v[keep] * ts_step_us) // win_us
    out = {}
    for c, w in zip(cmp_ids, wins):
        out[(int(c), int(w))] = out.get((int(c), int(w)), 0) + 1
    return out


class Collect:
    def __init__(self):
        self.rows = []
        self._lock = threading.Lock()

    def __call__(self, live):
        with self._lock:
            self.rows.extend(
                (int(r["key"]), int(r["count"]), int(r["lastUpdate"]),
                 int(r["revenue"]))
                for r in live)


def run_variant(variant, n_events=30000, pardegree2=4):
    got = Collect()
    pipe, sink, sent = build_pipeline(
        variant, duration_sec=0, pardegree1=1, pardegree2=pardegree2,
        win_sec=WIN_SEC, batches=fixed_batches(n_events), on_result=got)
    pipe.run_and_wait_end()
    return got, sink, sent


@pytest.mark.parametrize("variant", ["kf", "kf-tpu", "wmr", "wmr-tpu"])
def test_ysb_counts_match_oracle(variant):
    n = 30000
    got, sink, sent = run_variant(variant)
    assert sent[0] == n
    want = oracle_counts(n)
    # sum of per-window counts == number of filtered+joined events
    assert sum(c for _, c, *_ in got.rows) == sum(want.values())
    # per-campaign totals match
    per_cmp = {}
    for k, c, *_ in got.rows:
        per_cmp[k] = per_cmp.get(k, 0) + c
    want_cmp = {}
    for (c, _), n_ in want.items():
        want_cmp[c] = want_cmp.get(c, 0) + n_
    assert per_cmp == want_cmp
    assert sink.received == len(got.rows)


def test_ysb_kf_tpu_differential():
    """The device-path variant must produce the same windows as the host
    KF variant (count and lastUpdate)."""
    a, _, _ = run_variant("kf")
    b, _, _ = run_variant("kf-tpu")
    assert sorted(a.rows) == sorted(b.rows)


def test_ysb_kf_wmr_differential():
    """Both parallel decompositions produce identical (campaign, count)
    multisets — the test_all differential idea applied to YSB."""
    a, _, _ = run_variant("kf")
    b, _, _ = run_variant("wmr")
    assert sorted((k, c) for k, c, *_ in a.rows) == \
        sorted((k, c) for k, c, *_ in b.rows)


def test_ysb_last_update_is_window_max_ts():
    got, _, _ = run_variant("kf", n_events=5000)
    # for a linear ts ramp, each window's lastUpdate is the max filtered
    # event ts that fell into it; check against the oracle recomputation
    campaigns = CampaignGenerator()
    v = np.arange(5000, dtype=np.int64)
    vm = v % 100000
    keep = vm % 3 == 0
    cmp_ids = campaigns.ad_to_cmp[(vm % campaigns.n_ads)[keep]]
    ts = v[keep] * 50
    wins = ts // WIN_US
    want_max = {}
    for c, w, t in zip(cmp_ids, wins, ts):
        want_max[(int(c), int(w))] = max(want_max.get((int(c), int(w)), 0),
                                         int(t))
    # per-campaign multisets must pair up, not just the global multiset
    want_by_key = {}
    for (c, _), t in want_max.items():
        want_by_key.setdefault(c, []).append(t)
    got_by_key = {}
    for k, _, lu, _r in got.rows:
        got_by_key.setdefault(k, []).append(lu)
    assert {k: sorted(v) for k, v in got_by_key.items()} == \
        {k: sorted(v) for k, v in want_by_key.items()}


def test_ysb_aggregate_batch_matches_scalar():
    agg = YSBAggregate()
    rng = np.random.default_rng(0)
    rows = np.zeros(17, dtype=[("ts", np.int64), ("revenue", np.int64)])
    rows["ts"] = rng.integers(0, 1000, 17)
    rows["revenue"] = rng.integers(1, 98, 17)
    want = agg.apply(0, 0, rows)
    pad = 32
    ts_col = np.zeros((1, pad), dtype=np.int64)
    ts_col[0, :17] = rows["ts"]
    rev_col = np.zeros((1, pad), dtype=np.int64)
    rev_col[0, :17] = rows["revenue"]
    got = agg.apply_batch(np.zeros(1), np.zeros(1),
                          {"ts": ts_col, "revenue": rev_col},
                          np.array([17]))
    assert (int(got["count"][0]), int(got["lastUpdate"][0]),
            int(got["revenue"][0])) == want


def test_ysb_revenue_matches_oracle():
    """r3: the device-worthy SUM(revenue) must equal the per-campaign
    oracle on both the host and the device variants."""
    campaigns = CampaignGenerator()
    n = 30000
    v = np.arange(n, dtype=np.int64)
    vm = v % 100000
    keep = vm % 3 == 0
    cmp_ids = campaigns.ad_to_cmp[(vm % campaigns.n_ads)[keep]]
    rev = ((vm % 97) + 1)[keep]
    want_cmp = {}
    for c, r in zip(cmp_ids, rev):
        want_cmp[int(c)] = want_cmp.get(int(c), 0) + int(r)
    for variant in ("kf", "kf-tpu"):
        got, _, _ = run_variant(variant, n_events=n)
        per_cmp = {}
        for k, _c, _lu, r in got.rows:
            per_cmp[k] = per_cmp.get(k, 0) + r
        assert per_cmp == want_cmp, variant


def test_ysb_wmr_tpu_differential():
    """The device-MAP Win_MapReduce variant must produce the same windows
    (count, lastUpdate, revenue) as the host kf variant."""
    a, _, _ = run_variant("kf")
    b, _, _ = run_variant("wmr-tpu")
    assert sorted(a.rows) == sorted(b.rows)


def test_rich_stats_min_ts_is_host_free():
    """r5 (second half): MIN over the position field is as free as MAX —
    the position-ordered archive's first window row holds it — so
    device_aggregate(rich=True)'s firstUpdate no longer ships the ts
    column: the device half collapses back to the single revenue ring
    and BOTH extremes ride the pos-extrema split.  (The multi-field
    device path stays exercised by tests/test_native.py's multifield
    suite and chip_smoke.py's leg C.)"""
    import warnings

    from windflow_tpu.apps.ysb import device_aggregate
    from windflow_tpu.core.windows import WindowSpec, WinType
    from windflow_tpu.patterns.win_seq_tpu import make_core_for, \
        split_pos_max

    spec = WindowSpec(10_000_000, 10_000_000, WinType.TB)
    agg = device_aggregate(rich=True)
    dev, pos = split_pos_max(spec, agg)
    assert [p.field for p in dev] == ["revenue"]
    assert sorted((p.op, p.out_field) for p in pos) == [
        ("max", "lastUpdate"), ("min", "firstUpdate")]
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")
        core = make_core_for(spec, agg, batch_len=256)
    assert getattr(core, "_ship_fields", None) == ("revenue",)
