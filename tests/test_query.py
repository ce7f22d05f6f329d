"""repro.query — materialized aggregates, declarative queries, caching,
staleness, cold-range replay, and the asyncio serve surface.

The load-bearing guarantees:

  * hot answers equal a pure-Python fold of the same closed windows
  * cached answers equal uncached answers; a cache entry dies the
    moment the watermark or the materialized state moves
  * cold ranges (evicted beyond the retention floor) are recomputed
    from the EventLog through the Pallas batch path and agree with a
    pure-Python reference aggregation over the log
  * the staleness bound is enforced (StalenessExceeded + query_stale
    dead letter), never silently violated
  * async watch/alert iteration is event-driven: no thread per
    subscriber, no polling
"""
import asyncio
import math
import threading

import numpy as np
import pytest

from repro.alerts import AnalyticsStage, ThresholdRule, WindowSpec
from repro.alerts.windows import WindowAggregate
from repro.core import AlertMixPipeline, PipelineConfig
from repro.core.dead_letters import reason_in_taxonomy
from repro.query import (
    AggQuery,
    MaterializedStore,
    QueryPlane,
    StalenessExceeded,
)


def _stage(size_s=60.0, value_fn=None):
    return AnalyticsStage(
        WindowSpec(size_s=size_s), [],
        value_fn=value_fn or (lambda doc: float(doc.get("value", 1.0))))


def _feed(stage, events):
    """events: (channel, t, value) triples; advances past the last."""
    for ch, t, v in events:
        stage.observe({"channel": ch, "published_at": t, "value": v}, now=t)
    last = max(t for _, t, _ in events)
    stage.advance(last + 10 * stage.operator.spec.size_s)


# ---------------------------------------------------------------------------
# MaterializedStore
# ---------------------------------------------------------------------------

def test_store_ingest_merge_and_version():
    st = MaterializedStore()
    a = WindowAggregate("k", 0.0, 60.0)
    a.add(2.0), a.add(4.0)
    st.on_advance([a], watermark=60.0)
    assert st.version == 1 and st.watermark == 60.0
    assert st.status()["hot_segments"] == 1
    # a late re-close of the same slot MERGES, never duplicates
    b = WindowAggregate("k", 0.0, 60.0)
    b.add(10.0)
    st.on_advance([b], watermark=120.0)
    rows = st.lookup(["k"], 0.0, 60.0)["k"]
    (start, end, count, total, sumsq, mn, mx) = rows[0]
    assert (count, total, mn, mx) == (3, 16.0, 2.0, 10.0)
    assert st.stats["merged_windows"] == 1
    # watermark-only advance still bumps nothing but the watermark
    v = st.version
    st.on_advance([], watermark=500.0)
    assert st.watermark == 500.0 and st.version == v


def test_store_eviction_raises_floor():
    st = MaterializedStore(max_windows_per_key=3)
    for i in range(6):
        agg = WindowAggregate("k", i * 60.0, (i + 1) * 60.0)
        agg.add(1.0)
        st.on_advance([agg], watermark=(i + 1) * 60.0)
    s = st.status()
    assert s["hot_segments"] == 3
    assert s["evicted_windows"] == 3
    assert s["floor"] == 3 * 60.0          # newest evicted window's end
    # evicted ranges return nothing hot; retained ones do
    assert st.lookup(["k"], 0.0, 180.0) == {}
    assert len(st.lookup(["k"], 180.0, 360.0)["k"]) == 3


def test_store_lookup_prunes_by_time_and_key():
    st = MaterializedStore()
    for key in ("a", "b"):
        for i in range(10):
            agg = WindowAggregate(key, i * 60.0, (i + 1) * 60.0)
            agg.add(1.0)
            st.on_advance([agg], watermark=600.0)
    out = st.lookup(["a"], 120.0, 300.0)
    assert set(out) == {"a"}
    assert [(r[0], r[1]) for r in out["a"]] == [
        (120.0, 180.0), (180.0, 240.0), (240.0, 300.0)]
    assert st.lookup(["c"], 0.0, 600.0) == {}


# ---------------------------------------------------------------------------
# AggQuery + QueryEngine over a standalone stage
# ---------------------------------------------------------------------------

def test_aggquery_normalizes_and_validates():
    q1 = AggQuery(channel="c", start=0.0, end=60.0, keys=("b", "a", "b"))
    q2 = AggQuery(channel="c", start=0.0, end=60.0, keys=("a", "b"))
    assert q1 == q2 and hash(q1) == hash(q2)
    assert q1.effective_keys == ("a", "b")
    assert AggQuery(channel="c", start=0.0, end=60.0).effective_keys == ("c",)
    with pytest.raises(ValueError):
        AggQuery(channel="c", start=0.0, end=60.0, agg="p99")
    with pytest.raises(ValueError):
        AggQuery(channel="c", start=60.0, end=60.0)
    with pytest.raises(ValueError):
        AggQuery(channel="c", start=0.0, end=60.0, granularity=0.0)


def test_derived_aggregates_match_numpy():
    rng = np.random.default_rng(0)
    vals = rng.uniform(1.0, 9.0, size=40)
    stage = _stage(size_s=60.0)
    plane = QueryPlane(stage)
    # all 40 events in one window
    _feed(stage, [("c", 10.0 + 0.5 * i, float(v)) for i, v in enumerate(vals)])

    def one(agg):
        res = plane.query(AggQuery(channel="c", start=0.0, end=60.0, agg=agg))
        assert len(res.points) == 1
        return res.points[0]["value"]

    assert one("count") == 40
    assert one("sum") == pytest.approx(vals.sum())
    assert one("mean") == pytest.approx(vals.mean())
    assert one("max") == pytest.approx(vals.max())
    assert one("min") == pytest.approx(vals.min())
    assert one("stddev") == pytest.approx(vals.std(), rel=1e-6)
    assert one("rate") == pytest.approx(40 / 60.0)


def test_granularity_rebuckets_windows():
    stage = _stage(size_s=60.0)
    plane = QueryPlane(stage)
    # one event per minute for 10 minutes
    _feed(stage, [("c", i * 60.0 + 1.0, 1.0) for i in range(10)])
    fine = plane.query(AggQuery(channel="c", start=0.0, end=600.0))
    assert len(fine.points) == 10
    coarse = plane.query(AggQuery(channel="c", start=0.0, end=600.0,
                                  granularity=300.0))
    assert [(p["start"], p["count"]) for p in coarse.points] == [
        (0.0, 5), (300.0, 5)]
    assert coarse.points[0]["end"] == 300.0


def test_multi_key_query_emits_per_key_points():
    stage = _stage()
    plane = QueryPlane(stage)
    _feed(stage, [("a", 10.0, 1.0), ("a", 20.0, 1.0), ("b", 30.0, 1.0)])
    res = plane.query(AggQuery(channel="a", start=0.0, end=60.0,
                               keys=("a", "b")))
    got = {(p["key"], p["count"]) for p in res.points}
    assert got == {("a", 2), ("b", 1)}


# ---------------------------------------------------------------------------
# cache correctness (satellite): hit / invalidation / parity
# ---------------------------------------------------------------------------

def test_cache_hit_invalidation_and_parity():
    pipe = AlertMixPipeline(
        PipelineConfig(num_sources=150, analytics=True, query=True,
                       window_size_s=60.0), seed=0)
    pipe.run_for(1200.0)
    q = AggQuery(channel="news", start=0.0, end=1e9)
    first = pipe.query.query(q)
    assert first.cached is False and first.points
    # identical query -> cache hit, identical answer
    hit = pipe.query.query(q)
    assert hit.cached is True
    assert hit.points == first.points and hit.as_of == first.as_of
    # the uncached recomputation agrees exactly
    forced = pipe.query.query(q, use_cache=False)
    assert forced.cached is False
    assert forced.points == first.points
    st = pipe.query.status()
    assert st["cache_hits"] == 1 and st["cache_misses"] == 1
    # watermark advance invalidates: same query recomputes, fresher as_of
    pipe.run_for(120.0)
    after = pipe.query.query(q)
    assert after.cached is False
    assert after.as_of > first.as_of
    assert pipe.query.status()["cache_misses"] == 2


def test_cache_is_lru_bounded():
    stage = _stage()
    plane = QueryPlane(stage, cache_entries=4)
    _feed(stage, [("c", 10.0, 1.0)])
    for i in range(10):
        plane.query(AggQuery(channel="c", start=0.0, end=60.0 + i))
    assert plane.engine.cache_len() == 4


# ---------------------------------------------------------------------------
# staleness bound
# ---------------------------------------------------------------------------

def test_staleness_bound_rejects_and_dead_letters():
    pipe = AlertMixPipeline(
        PipelineConfig(num_sources=50, analytics=True, query=True,
                       window_size_s=60.0, query_staleness_s=120.0), seed=0)
    pipe.run_for(600.0)
    q = AggQuery(channel="news", start=0.0, end=600.0)
    pipe.query.query(q)                      # fresh: fine
    pipe.now += 100_000.0                    # clock runs away, no analytics
    with pytest.raises(StalenessExceeded) as ei:
        pipe.query.query(q)
    assert ei.value.lag_s > ei.value.bound_s == 120.0
    assert pipe.dead_letters.by_reason["query_stale"] == 1
    assert reason_in_taxonomy("query_stale")
    assert pipe.query.status()["stale_rejected"] == 1


# ---------------------------------------------------------------------------
# hot answers vs a pure-Python fold (pipeline-driven)
# ---------------------------------------------------------------------------

def _reference_counts(pipe, channel, start, end):
    """Pure-Python per-window counts over the EventLog for one channel,
    restricted to windows the operator has closed."""
    spec = pipe.analytics.operator.spec
    horizon = (pipe.analytics.operator.watermark
               - spec.allowed_lateness_s)
    ref = {}
    for _off, payload in pipe.store.log.scan():
        doc = payload["doc"]
        if doc.get("channel") != channel or "key" in doc:
            continue
        t = float(doc["published_at"])
        for s, e in spec.assign(t):
            if e <= start or s >= end or e > horizon:
                continue
            ref[(s, e)] = ref.get((s, e), 0) + 1
    return ref


def test_hot_query_matches_reference():
    import tempfile
    pipe = AlertMixPipeline(
        PipelineConfig(num_sources=200, analytics=True, query=True,
                       store_dir=tempfile.mkdtemp(), window_size_s=60.0),
        seed=0)
    try:
        pipe.run_for(1800.0)
        res = pipe.query.query(AggQuery(channel="news", start=0.0, end=1800.0))
        assert res.source == "hot"
        got = {(p["start"], p["end"]): p["count"] for p in res.points}
        assert got == _reference_counts(pipe, "news", 0.0, 1800.0)
    finally:
        pipe.close()


# ---------------------------------------------------------------------------
# cold-range queries: evicted windows answered via EventLog + kernel path
# (acceptance criterion (c): result parity vs pure-Python reference)
# ---------------------------------------------------------------------------

def test_cold_range_query_parity_with_reference():
    import tempfile
    pipe = AlertMixPipeline(
        PipelineConfig(num_sources=200, analytics=True, query=True,
                       store_dir=tempfile.mkdtemp(), window_size_s=60.0,
                       query_max_windows_per_key=5), seed=0)
    try:
        pipe.run_for(2400.0)
        st = pipe.query.status()
        assert st["evicted_windows"] > 0 and st["floor"] > 0.0
        res = pipe.query.query(AggQuery(channel="news", start=0.0, end=2400.0))
        # the full range spans evicted + retained windows
        assert res.source == "mixed"
        assert pipe.query.status()["cold_scans"] == 1
        got = {(p["start"], p["end"]): p["count"] for p in res.points}
        assert got == _reference_counts(pipe, "news", 0.0, 2400.0)
        # a purely-cold range too
        floor = st["floor"]
        cold = pipe.query.query(
            AggQuery(channel="news", start=0.0, end=min(floor, 300.0)))
        assert cold.source == "cold"
        cg = {(p["start"], p["end"]): p["count"] for p in cold.points}
        assert cg == _reference_counts(pipe, "news", 0.0, min(floor, 300.0))
        # value lanes agree with numpy within float32 tolerance
        sums = {(p["start"]): p["value"]
                for p in pipe.query.query(
                    AggQuery(channel="news", start=0.0, end=2400.0,
                             agg="sum")).points}
        for (s, e), n in got.items():
            assert sums[s] == pytest.approx(float(n), rel=1e-5)
    finally:
        pipe.close()


def test_cold_range_query_parity_on_columnar_store():
    """Same parity bar, columnar route: cold scans read block lanes
    (block-stat pruned, vectorized pack) instead of per-record decode,
    and the answer must match the pure-Python reference bit for bit."""
    import tempfile
    pipe = AlertMixPipeline(
        PipelineConfig(num_sources=200, analytics=True, query=True,
                       store_dir=tempfile.mkdtemp(), store_columnar=True,
                       columnar_block_rows=64, segment_bytes=1 << 14,
                       window_size_s=60.0, query_max_windows_per_key=5),
        seed=0)
    try:
        pipe.run_for(2400.0)
        st = pipe.query.status()
        assert st["evicted_windows"] > 0 and st["floor"] > 0.0
        assert pipe.query.engine.columnar_lanes is True
        res = pipe.query.query(
            AggQuery(channel="news", start=0.0, end=2400.0))
        assert res.source == "mixed"
        assert pipe.query.status()["cold_columnar"] == 1
        got = {(p["start"], p["end"]): p["count"] for p in res.points}
        assert got == _reference_counts(pipe, "news", 0.0, 2400.0)
        # sealed segments really are columnar (the fast path ran on
        # blocks, not a JSON fallback)
        assert pipe.store_stats()["columnar"]["sealed_columnar_segments"] > 0
    finally:
        pipe.close()


def test_cold_query_without_store_stays_hot_only():
    pipe = AlertMixPipeline(
        PipelineConfig(num_sources=100, analytics=True, query=True,
                       window_size_s=60.0, query_max_windows_per_key=3),
        seed=0)
    pipe.run_for(1200.0)
    assert pipe.query.status()["floor"] > 0.0
    res = pipe.query.query(AggQuery(channel="news", start=0.0, end=1200.0))
    # no EventLog: evicted windows are simply gone; no crash, no cold scan
    assert res.source == "hot"
    assert pipe.query.status()["cold_scans"] == 0


def test_cold_query_refuses_session_windows():
    """A cold scan reads the range widened by the spec's size, which
    bounds no session: a session spec is refused, not answered with
    sessions cut at the range's edges."""
    from repro.query.engine import QueryEngine
    store = MaterializedStore(max_windows_per_key=1)
    store.on_advance([WindowAggregate("a", 0.0, 40.0, count=3),
                      WindowAggregate("a", 100.0, 110.0, count=1)], 200.0)
    assert store.floor == 40.0
    eng = QueryEngine(store, spec=WindowSpec(kind="session", gap_s=10.0),
                      log=object())
    with pytest.raises(ValueError, match="session windows"):
        eng.query(AggQuery(channel="a", start=0.0, end=200.0))
    # a range the hot store still holds needs no cold scan
    res = eng.query(AggQuery(channel="a", start=50.0, end=200.0))
    assert res.source == "hot" and eng.status()["cold_scans"] == 0


# ---------------------------------------------------------------------------
# replayed late events merge into serving state (export hook from replay)
# ---------------------------------------------------------------------------

def test_late_replay_merges_into_materialized_store():
    import tempfile
    pipe = AlertMixPipeline(
        PipelineConfig(num_sources=0, analytics=True, query=True,
                       store_dir=tempfile.mkdtemp(), window_size_s=60.0,
                       allowed_lateness_s=0.0, watermark_lag_s=0.0), seed=0)
    try:
        stage = pipe.analytics
        # live events close window [0, 60)
        stage.observe({"channel": "c", "published_at": 10.0}, now=10.0)
        pipe.run_for(300.0)
        res = pipe.query.query(AggQuery(channel="c", start=0.0, end=60.0))
        assert res.points[0]["count"] == 1
        # a late event for that window dead-letters, then the flush
        # drains it through the batch path — the export hook must fold
        # the replayed aggregate into the SAME materialized slot
        assert stage.observe({"channel": "c", "published_at": 20.0},
                             now=pipe.now) is False
        pipe.flush_delivery()
        res2 = pipe.query.query(AggQuery(channel="c", start=0.0, end=60.0))
        assert res2.points[0]["count"] == 2
        assert pipe.query.store.stats["merged_windows"] == 1
    finally:
        pipe.close()


# ---------------------------------------------------------------------------
# asyncio surfaces: watch, alert iteration, no thread per subscriber
# ---------------------------------------------------------------------------

def test_watch_streams_updates_on_store_change():
    pipe = AlertMixPipeline(
        PipelineConfig(num_sources=150, analytics=True, query=True,
                       window_size_s=60.0), seed=0)
    pipe.run_for(600.0)
    q = AggQuery(channel="news", start=0.0, end=1e9)

    async def main():
        results = []

        async def watcher():
            async for res in pipe.query.watch(q, max_updates=3):
                results.append(res)

        task = asyncio.create_task(watcher())
        await asyncio.sleep(0)
        for _ in range(300):
            pipe.step(5.0)
            await asyncio.sleep(0)
            if task.done():
                break
        await asyncio.wait_for(task, 5)
        return results

    results = asyncio.run(main())
    assert len(results) == 3
    # monotone freshness, growing (or equal) data
    assert results[0].as_of < results[-1].as_of
    assert (sum(p["count"] for p in results[-1].points)
            >= sum(p["count"] for p in results[0].points))
    # the watcher detached its listener on exit
    assert pipe.query.store._listeners == []


def test_async_subscribers_do_not_spawn_threads():
    """The asyncio bridge parks coroutines, not threads: 64 concurrent
    subscribers (query watchers + alert iterators) leave the process
    thread count untouched."""
    pipe = AlertMixPipeline(
        PipelineConfig(num_sources=150, analytics=True, query=True,
                       window_size_s=60.0), seed=0,
        analytics_rules=[ThresholdRule("vol", metric="count", op=">=",
                                       threshold=1.0)])
    pipe.run_for(300.0)
    before = threading.active_count()

    async def main():
        q = AggQuery(channel="news", start=0.0, end=1e9)
        seen = [0, 0]

        async def watch_one():
            async for _ in pipe.query.watch(q, max_updates=1):
                seen[0] += 1

        async def alerts_one():
            async for _ in pipe.analytics.hub.async_iter("vol"):
                seen[1] += 1
                return

        tasks = [asyncio.create_task(watch_one()) for _ in range(32)]
        tasks += [asyncio.create_task(alerts_one()) for _ in range(32)]
        await asyncio.sleep(0)
        during = threading.active_count()
        for _ in range(300):
            pipe.step(5.0)
            await asyncio.sleep(0)
            if all(t.done() for t in tasks):
                break
        await asyncio.wait_for(asyncio.gather(*tasks), 10)
        return during, seen

    during, seen = asyncio.run(main())
    assert during == before == threading.active_count()
    assert seen[0] == 32 and seen[1] == 32


def test_subscription_async_iteration_and_close():
    from repro.delivery import SubscriptionHub

    hub = SubscriptionHub()

    async def main():
        sub = hub.subscribe(capacity=8)
        got = []

        async def consume():
            async for rec in sub:
                got.append(rec)

        task = asyncio.create_task(consume())
        await asyncio.sleep(0)
        hub.emit(["a", "b"])
        await asyncio.sleep(0.01)
        hub.emit(["c"])
        await asyncio.sleep(0.01)
        sub.close()                      # ends the async iteration
        await asyncio.wait_for(task, 2)
        return got

    assert asyncio.run(main()) == ["a", "b", "c"]
    assert hub.subscriber_count == 0


def test_async_iteration_rejects_callback_mode():
    from repro.delivery import SubscriptionHub

    hub = SubscriptionHub()
    sub = hub.subscribe(lambda rec: None)

    async def main():
        async for _ in sub:
            pass

    with pytest.raises(RuntimeError):
        asyncio.run(main())


# ---------------------------------------------------------------------------
# alerts_history retention cap (satellite)
# ---------------------------------------------------------------------------

def test_alerts_history_caps_fired_retention():
    rules = [ThresholdRule("every_window", metric="count", op=">=",
                           threshold=1.0)]
    pipe = AlertMixPipeline(
        PipelineConfig(num_sources=100, analytics=True, window_size_s=30.0,
                       alerts_history=7), seed=0, analytics_rules=rules)
    pipe.run_for(3600.0)
    total = pipe.analytics.sink.by_rule["every_window"]
    assert total > 7                     # enough fired to exercise the cap
    assert len(pipe.alerts) == 7         # retention bounded...
    assert pipe.metrics.alerts_total == total   # ...totals stay complete
    assert pipe.alerts[-1].window_end == max(
        a.window_end for a in pipe.alerts)


# ---------------------------------------------------------------------------
# min lane: live operator vs batch kernel path
# ---------------------------------------------------------------------------

def test_min_lane_live_and_batch_agree():
    from repro.alerts.batch import reduce_events
    from repro.alerts.windows import WindowOperator

    rng = np.random.default_rng(1)
    events = [("k", float(t), float(v)) for t, v in zip(
        rng.uniform(0.0, 300.0, 200), rng.uniform(-5.0, 5.0, 200))]
    spec = WindowSpec(size_s=60.0)
    op = WindowOperator(spec)
    for k, t, v in events:
        op.observe(k, t, v)
    op.advance_watermark(1e6)
    live = {(a.window_start, a.window_end): (a.min, a.max)
            for a in op.poll_closed()}
    batch = {(a.window_start, a.window_end): (a.min, a.max)
             for a in reduce_events(events, spec, with_min=True)}
    assert set(live) == set(batch)
    for slot, (mn, mx) in live.items():
        assert batch[slot][0] == pytest.approx(mn, rel=1e-6)
        assert batch[slot][1] == pytest.approx(mx, rel=1e-6)


def test_cold_query_launches_count_under_the_query_route():
    """A cold-range query counts its kernel launches under ``query``;
    the same query again launches the same shape, not a new one."""
    import tempfile

    from repro.obs import kernel_launches

    def counts():
        return kernel_launches().get("window_reduce", {})

    def grew(a, b, route, key):
        return b.get(route, {}).get(key, 0) - a.get(route, {}).get(key, 0)

    pipe = AlertMixPipeline(
        PipelineConfig(num_sources=200, analytics=True, query=True,
                       store_dir=tempfile.mkdtemp(), store_columnar=True,
                       columnar_block_rows=64, segment_bytes=1 << 14,
                       window_size_s=60.0, query_max_windows_per_key=5),
        seed=0)
    try:
        pipe.run_for(2400.0)
        q = AggQuery(channel="news", start=0.0, end=2400.0, agg="min")
        c0 = counts()
        pipe.query.query(q, use_cache=False)
        c1 = counts()
        pipe.query.query(q, use_cache=False)
        c2 = counts()
    finally:
        pipe.close()
    assert grew(c0, c1, "query", "launches") == 2      # max and min lanes
    # one shape for both lanes (none new if this process launched it)
    assert grew(c0, c1, "query", "new_shapes") <= 1
    assert c1["query"]["new_shapes"] >= 1
    assert grew(c1, c2, "query", "launches") == 2
    assert grew(c1, c2, "query", "new_shapes") == 0
    for route in ("replay", "drain"):
        assert grew(c0, c2, route, "launches") == 0
    text = pipe.metrics_text()
    assert 'kernel_launches_total{kernel="window_reduce",route="query"}' \
        in text
