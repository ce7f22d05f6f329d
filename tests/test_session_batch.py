"""Session windows on the batch path against two references.

``pack_columns`` lays session windows out by sorting the events by
(key, event time) and cutting where the key changes or the next event
comes more than ``gap_s`` later.  The kernel's aggregates of that layout
(``reduce_columns``, interpret mode) must equal a per-key Python
sessionizer with a float64 group-by, written here, and the live
``WindowOperator`` fed the same events in a shuffled order: key, start,
end, count, max and min exactly, sum and sum of squares within the
float32 accumulation bound.
"""
import numpy as np
import pytest

from repro.alerts import WindowOperator, WindowSpec
from repro.alerts.batch import pack_columns, pack_events, reduce_columns
from repro.core import AlertMixPipeline, PipelineConfig
from repro.obs import pack_sessions, pack_slot_index

GAP = 10.0
SPEC = WindowSpec(kind="session", gap_s=GAP, allowed_lateness_s=0.0)
U32 = 2.0 ** -24


def python_sessions(keys, ts, values, gap_s):
    """{(key, start): (end, count, sum, sumsq, max, min)}: each key's
    events in time order, a new session wherever an event comes more
    than ``gap_s`` after the session's last one (float64 sums)."""
    per_key = {}
    for k, t, v in zip(keys, ts, values):
        per_key.setdefault(k, []).append((float(t), float(v)))
    out = {}
    for k, evs in per_key.items():
        evs.sort()
        cur = None
        for t, v in evs:
            if cur is None or t > cur["last"] + gap_s:
                cur = {"start": t, "last": t, "vals": []}
                out[(k, t)] = cur
            cur["last"] = t
            cur["vals"].append(v)
    return {slot: (s["last"] + gap_s, len(s["vals"]), sum(s["vals"]),
                   sum(v * v for v in s["vals"]), max(s["vals"]),
                   min(s["vals"]))
            for slot, s in out.items()}


def live_sessions(keys, ts, values, spec, seed):
    """The live operator fed the events in a shuffled order, then closed
    past the last gap."""
    op = WindowOperator(spec)
    for i in np.random.default_rng(seed).permutation(len(ts)):
        assert op.observe(str(keys[i]), float(ts[i]), float(values[i]))
    op.advance_watermark(float(np.max(ts)) + spec.gap_s + 1.0)
    return {(a.key, a.window_start): (a.window_end, a.count, a.sum,
                                      a.sumsq, a.max, a.min)
            for a in op.poll_closed()}


def batch_sessions(keys, ts, values, spec):
    vocab, codes = np.unique(np.asarray(keys, object), return_inverse=True)
    aggs = reduce_columns(np.asarray(ts, np.float64), codes,
                          np.asarray(values, np.float64), list(vocab), spec,
                          interpret=True, with_min=True)
    out = {}
    for a in aggs:
        assert (a.key, a.window_start) not in out
        out[(a.key, a.window_start)] = (a.window_end, a.count, a.sum,
                                        a.sumsq, a.max, a.min)
    return out


def _gamma(n):
    return n * U32 / (1.0 - n * U32)


def assert_same_sessions(got, want, keys, ts, values):
    """Slots, ends, counts and extremes exact; sum and sumsq within the
    float32 bound for the session's n terms in any order (Higham, 4.2),
    plus the float64 reference's own rounding."""
    assert sorted(got) == sorted(want)
    mags = python_sessions(keys, ts, np.abs(values), GAP)
    for slot, (end, n, s, sq, mx, mn) in want.items():
        g_end, g_n, g_s, g_sq, g_mx, g_mn = got[slot]
        assert (g_end, g_n, g_mx, g_mn) == (end, n, mx, mn), slot
        sum_abs, sum_sq = mags[slot][2], mags[slot][3]
        assert abs(g_s - s) <= (_gamma(n - 1) + 2 * n * 2.0 ** -53) \
            * sum_abs, slot
        assert abs(g_sq - sq) <= (_gamma(n) + 2 * n * 2.0 ** -53) \
            * sum_sq, slot


def _values(rng, n):
    """Prices in quarter cents: exact in float32, so max and min compare
    exactly."""
    return np.round(rng.random(n) * 4e5) / 4.0


def _random(seed, n, n_keys, t0, span):
    rng = np.random.default_rng(seed)
    keys = [f"u{k}" for k in rng.integers(0, n_keys, n)]
    return keys, t0 + rng.random(n) * span, _values(rng, n)


def _exact(pairs, values=None):
    keys = [k for k, _ in pairs]
    ts = np.array([t for _, t in pairs], np.float64)
    vals = (np.arange(1, len(pairs) + 1, dtype=np.float64) * 2.5
            if values is None else np.asarray(values, np.float64))
    return keys, ts, vals


CASES = {
    # bids exactly gap_s apart share a session (closed intervals)
    "exact_gap_merges": _exact([("a", 0.0), ("a", 10.0), ("a", 20.0)]),
    # just over the gap opens a new one
    "just_over_gap_cuts": _exact([("a", 0.0), ("a", np.nextafter(10.0,
                                                                 11.0)),
                                  ("a", 30.0)]),
    # the middle bid bridges two sessions that were apart
    "bridging_bid": _exact([("a", 0.0), ("a", 18.0), ("a", 9.0),
                            ("b", 100.0), ("b", 115.0)]),
    "equal_timestamps": _exact([("a", 5.0)] * 4 + [("b", 5.0)] * 3
                               + [("a", 15.0), ("a", 25.5)]),
    "unsorted": _exact([("b", 40.0), ("a", 3.0), ("b", 31.0),
                        ("a", 50.0), ("a", 12.5), ("b", 20.0)]),
    "one_event": _exact([("solo", 123.25)]),
    "negative_timestamps": _exact([("a", -100.0), ("a", -90.0),
                                   ("a", -79.5), ("b", -5.0),
                                   ("b", 4.0), ("a", -69.5)]),
    "many_keys": _random(3, 3000, 400, 0.0, 600.0),
    "seeded_sparse": _random(5, 2000, 20, -1000.0, 4000.0),
    "seeded_dense": _random(7, 4000, 8, 1e6, 200.0),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_batch_sessions_match_python_and_live(case):
    keys, ts, vals = CASES[case]
    got = batch_sessions(keys, ts, vals, SPEC)
    assert_same_sessions(got, python_sessions(keys, ts, vals, GAP),
                         keys, ts, vals)
    assert_same_sessions(got, live_sessions(keys, ts, vals, SPEC,
                                            seed=len(ts)), keys, ts, vals)


def test_case_shapes():
    """The hand-written cases are what their names say."""
    got = batch_sessions(*CASES["exact_gap_merges"], SPEC)
    assert list(got) == [("a", 0.0)] and got[("a", 0.0)][:2] == (30.0, 3)
    assert len(batch_sessions(*CASES["just_over_gap_cuts"], SPEC)) == 3
    bridged = batch_sessions(*CASES["bridging_bid"], SPEC)
    assert bridged[("a", 0.0)][:2] == (28.0, 3)
    assert len(bridged) == 3 and bridged[("b", 100.0)][1] == 1


@pytest.mark.parametrize("spec", [
    WindowSpec(kind="tumbling", size_s=60.0),
    WindowSpec(kind="sliding", size_s=60.0, slide_s=20.0),
    SPEC], ids=["tumbling", "sliding", "session"])
def test_pack_events_equals_pack_columns(spec):
    """``pack_events`` lays triples out as ``pack_columns`` lays out their
    keys coded in sorted order, for every window kind; each membership
    lands in a slot that holds its event (``WindowSpec.assign``'s slots,
    one membership per slot and event, for the fixed kinds)."""
    keys, ts, vals = CASES["many_keys"]
    vocab, codes = np.unique(np.asarray(keys, object), return_inverse=True)
    packed, seg_ids, slots = pack_columns(ts, codes, vals, spec)
    e_packed, e_seg_ids, e_slots = pack_events(list(zip(keys, ts, vals)),
                                               spec)
    assert packed.tobytes() == e_packed.tobytes()
    assert seg_ids.tobytes() == e_seg_ids.tobytes()
    assert e_slots == [(vocab[c], s, e) for c, s, e in slots]
    assert all(type(k) is str for k, _, _ in e_slots)
    assert e_slots == sorted(e_slots, key=lambda s: (s[1], s[0]))
    if spec.kind == "session":
        return
    want = sorted((k, s, e, float(np.float32(v)))
                  for k, t, v in zip(keys, ts, vals)
                  for s, e in spec.assign(float(t)))
    got = sorted(e_slots[i] + (v,) for i, v in zip(e_seg_ids.tolist(),
                                                   e_packed.tolist()))
    assert got == want


def test_session_layout_order_and_counters():
    """Slots in (start, code) order, seg ids index them, and the pack
    counts its path once and its sessions by what opened them."""
    keys, ts, vals = CASES["seeded_sparse"]
    vocab, codes = np.unique(np.asarray(keys, object), return_inverse=True)
    paths0, cuts0 = pack_slot_index(), pack_sessions()
    packed, seg_ids, slots = pack_columns(ts, codes, vals, SPEC)
    paths1, cuts1 = pack_slot_index(), pack_sessions()
    assert {p: paths1[p] - paths0[p] for p in paths1} == \
        {"dense": 0, "sort": 0, "session": 1}
    opened = {c: cuts1[c] - cuts0[c] for c in cuts1}
    assert opened["key"] == len(vocab)
    assert opened["key"] + opened["gap"] == len(slots)
    assert opened["gap"] > 0
    assert slots == sorted(slots, key=lambda s: (s[1], s[0]))
    for i in range(ts.size):
        c, s, e = slots[seg_ids[i]]
        assert c == codes[i] and s <= ts[i] and ts[i] + GAP <= e
    assert packed.dtype == np.float32 and seg_ids.dtype == np.int32


def test_empty_session_pack_counts_nothing():
    paths0, cuts0 = pack_slot_index(), pack_sessions()
    packed, seg_ids, slots = pack_columns(np.empty(0), np.empty(0, np.int64),
                                          np.empty(0), SPEC)
    assert packed.size == seg_ids.size == 0 and slots == []
    assert pack_slot_index() == paths0 and pack_sessions() == cuts0


def test_pipeline_window_gap_reaches_the_spec():
    for kw, gap in (({"window_gap_s": 7.5}, 7.5), ({}, 30.0)):
        p = AlertMixPipeline(PipelineConfig(num_sources=0, analytics=True,
                                            window_kind="session", **kw),
                             seed=0)
        try:
            spec = p.analytics.operator.spec
            assert spec.kind == "session" and spec.gap_s == gap
        finally:
            p.close()


def test_columnar_replay_log_equals_the_live_operator(tmp_path):
    """A sealed columnar log of session-keyed documents replays through
    ``replay_log`` into what the live operator closes for the same
    documents, and every scrape exports the session pack's counters."""
    keys, ts, vals = _random(11, 1500, 30, 0.0, 900.0)
    p = AlertMixPipeline(
        PipelineConfig(num_sources=0, analytics=True, window_kind="session",
                       window_gap_s=GAP, allowed_lateness_s=0.0,
                       watermark_lag_s=0.0, store_columnar=True,
                       store_dir=str(tmp_path / "s")), seed=0)
    try:
        p.store.append_documents(
            [(f"d{i}", {"key": keys[i], "value": float(vals[i]),
                        "published_at": float(ts[i])})
             for i in range(ts.size)])
        p.store.log.roll()
        exported = []
        p.analytics.add_export(lambda closed, wm: exported.extend(closed))
        cuts0 = pack_sessions()
        res = p.store.replay.replay_log(0, watermark=1e9)
        cuts1 = pack_sessions()
        assert res["columnar"] and res["events"] == ts.size
        got = {(a.key, a.window_start): (a.window_end, a.count, a.sum,
                                         a.sumsq, a.max, a.min)
               for a in exported}
        assert len(got) == len(exported) == res["aggregates"]
        assert sum(cuts1[c] - cuts0[c] for c in cuts1) == len(got)
        assert_same_sessions(got, live_sessions(keys, ts, vals, SPEC, 1),
                             keys, ts, vals)
        text = p.metrics_text()
        assert 'pack_slot_index_total{path="session"}' in text
        for cut in ("key", "gap"):
            assert f'pack_sessions_total{{cut="{cut}"}}' in text
        reg = p.obs.metrics
        assert reg.counter("pack_sessions_total").value(cut="gap") == \
            cuts1["gap"]
        assert reg.counter("pack_slot_index_total").value(
            path="session") == pack_slot_index()["session"]
    finally:
        p.close()


def test_the_comparison_refuses_bfloat16_sums():
    """The bound the parity tests use is tight enough to catch lanes
    summed from values rounded to bfloat16 (extremes kept exact)."""
    import ml_dtypes
    keys, ts, vals = CASES["seeded_dense"]
    want = python_sessions(keys, ts, vals, GAP)
    bf16 = vals.astype(ml_dtypes.bfloat16).astype(np.float64)
    got = {slot: (g[0], g[1], g[2], g[3], want[slot][4], want[slot][5])
           for slot, g in batch_sessions(keys, ts, bf16, SPEC).items()}
    with pytest.raises(AssertionError):
        assert_same_sessions(got, want, keys, ts, vals)
