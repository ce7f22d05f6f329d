"""Query/serving-plane benchmark (repro.query): what answering
dashboards costs.

  cache leverage       identical-query throughput with the watermark-
                       invalidated result cache vs forced recomputation
                       over the same materialized segments — the
                       acceptance bar is >= 100x (a million identical
                       dashboard panels must cost one aggregation),
                       asserted below in full mode
  concurrency          queries/s sustained by a foreground querier
                       while 1 / 16 / 64 asyncio subscribers watch live
                       queries and alert streams, with the staleness
                       bound asserted on every answer (stale_rejected
                       must stay 0) and zero threads per subscriber
  cold-range replay    queries below the retention floor answered by
                       EventLog scan + the Pallas window_reduce batch
                       path, with result parity vs a pure-Python
                       reference aggregation asserted here (and in
                       tests/test_query.py); a second round runs the
                       same query against a columnar store, where the
                       cold scan rides block-stat-pruned numpy lanes

Writes machine-readable results to ``BENCH_query.json`` (CI uploads it
as an artifact so trajectories accumulate across commits).

  PYTHONPATH=src python -m benchmarks.bench_query            # full
  PYTHONPATH=src python -m benchmarks.bench_query --smoke    # CI smoke
"""
from __future__ import annotations

import asyncio
import json
import shutil
import sys
import tempfile
import threading
import time

from repro.core import AlertMixPipeline, PipelineConfig
from repro.query import AggQuery

# THE acceptance bar: a cached identical query answers >= 100x faster
# than recomputing its aggregation (full mode; smoke keeps a sanity
# floor — tiny runs materialize too few segments to show the full gap)
CACHE_BAR = 100.0
CACHE_BAR_SMOKE = 10.0
STALENESS_BOUND_S = 900.0


def _drive(num_sources: int, virtual_s: float, *, window_s: float = 30.0,
           store: bool = False, retention: int = 1 << 16,
           columnar: bool = False) -> tuple:
    d = tempfile.mkdtemp(prefix="bench_query_") if store else None
    p = AlertMixPipeline(PipelineConfig(
        num_sources=num_sources, feed_interval_s=300.0,
        queue_capacity=max(200_000, num_sources * 2),
        analytics=True, query=True, window_size_s=window_s,
        query_staleness_s=STALENESS_BOUND_S,
        query_max_windows_per_key=retention,
        store_dir=d, store_columnar=columnar), seed=0)
    p.run_for(virtual_s, dt=5.0)
    return p, d


def bench_cache_leverage(num_sources: int, virtual_s: float,
                         cached_iters: int, uncached_iters: int) -> dict:
    """Identical-query throughput: cache hit vs forced recompute."""
    p, _ = _drive(num_sources, virtual_s)
    try:
        q = AggQuery(channel="news", start=0.0, end=virtual_s)
        res = p.query.query(q)                    # warm the cache
        segments = p.query.status()["hot_segments"]
        t0 = time.perf_counter()
        for _ in range(cached_iters):
            p.query.query(q)
        cached_dt = time.perf_counter() - t0
        t0 = time.perf_counter()
        for _ in range(uncached_iters):
            forced = p.query.query(q, use_cache=False)
        uncached_dt = time.perf_counter() - t0
        assert forced.points == res.points        # parity, not shortcut
        cached_qps = cached_iters / cached_dt
        uncached_qps = uncached_iters / uncached_dt
        return {"cached_qps": cached_qps, "uncached_qps": uncached_qps,
                "speedup": cached_qps / uncached_qps,
                "hot_segments": segments,
                "points": len(res.points),
                "cache_hits": p.query.status()["cache_hits"]}
    finally:
        p.close()


async def _concurrency_round(p, n_subs: int, duration_s: float) -> dict:
    """Foreground querier throughput while ``n_subs`` asyncio watchers
    consume live query + alert streams and the pipeline keeps running."""
    channels = ("news", "custom_rss", "facebook", "twitter")
    watch_updates = [0]

    async def watcher(i: int):
        q = AggQuery(channel=channels[i % len(channels)],
                     start=0.0, end=1e12, agg="rate", granularity=300.0)
        async for _res in p.query.watch(q):
            watch_updates[0] += 1

    threads_before = threading.active_count()
    tasks = [asyncio.create_task(watcher(i)) for i in range(n_subs)]
    await asyncio.sleep(0)
    threads_during = threading.active_count()

    q_main = AggQuery(channel="news", start=0.0, end=1e12)
    queries = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < duration_s:
        p.step(5.0)                       # virtual time keeps flowing
        for _ in range(50):
            res = p.query.query(q_main)   # staleness gate asserts bound
            assert p.now - res.as_of <= STALENESS_BOUND_S
            queries += 1
        await asyncio.sleep(0)            # let watchers drain
    wall = time.perf_counter() - t0
    for t in tasks:
        t.cancel()
    await asyncio.gather(*tasks, return_exceptions=True)
    st = p.query.status()
    return {"subscribers": n_subs, "queries_s": queries / wall,
            "watch_updates": watch_updates[0],
            "stale_rejected": st["stale_rejected"],
            "threads_added": threads_during - threads_before,
            "staleness_bound_s": STALENESS_BOUND_S}


def bench_concurrency(num_sources: int, virtual_s: float,
                      duration_s: float) -> list:
    out = []
    for n_subs in (1, 16, 64):
        p, _ = _drive(num_sources, virtual_s, window_s=60.0)
        try:
            out.append(asyncio.run(_concurrency_round(p, n_subs,
                                                      duration_s)))
        finally:
            p.close()
    return out


def bench_cold_range(num_sources: int, virtual_s: float,
                     iters: int, *, columnar: bool = False) -> dict:
    """Queries below the retention floor: the log's ``scan_lanes`` +
    kernel path, with parity vs a pure-Python fold of the same log
    asserted.  With ``columnar=True`` the store is a ColumnarEventLog,
    whose scan decodes block-stat-pruned numpy lanes; the JSON EventLog
    reads its records row by row into the same lanes."""
    p, d = _drive(num_sources, virtual_s, store=True, retention=16,
                  columnar=columnar)
    try:
        st = p.query.status()
        assert st["floor"] > 0.0, "retention never evicted; no cold range"
        q = AggQuery(channel="news", start=0.0, end=st["floor"])
        res = p.query.query(q, use_cache=False)
        assert res.source in ("cold", "mixed")
        # pure-Python reference over the same log (acceptance parity)
        spec = p.analytics.operator.spec
        horizon = p.analytics.operator.watermark - spec.allowed_lateness_s
        ref = {}
        for _off, payload in p.store.log.scan():
            doc = payload["doc"]
            if doc.get("channel") != "news" or "key" in doc:
                continue
            t = float(doc["published_at"])
            for s, e in spec.assign(t):
                if e <= q.start or s >= q.end or e > horizon:
                    continue
                ref[(s, e)] = ref.get((s, e), 0) + 1
        got = {(pt["start"], pt["end"]): pt["count"] for pt in res.points}
        assert got == ref, "cold-range counts diverge from the reference"
        t0 = time.perf_counter()
        for _ in range(iters):
            p.query.query(q, use_cache=False)
        dt = time.perf_counter() - t0
        stq = p.query.status()
        return {"cold_qps": iters / dt,
                "cold_events_per_scan": stq["cold_events"] // stq["cold_scans"],
                "evicted_windows": stq["evicted_windows"],
                "floor": stq["floor"], "windows": len(got),
                "parity_ok": True}
    finally:
        p.close()
        shutil.rmtree(d, ignore_errors=True)


def main(rows, *, smoke: bool = False):
    if smoke:
        srcs, vs, cached_iters, uncached_iters = 800, 10_800.0, 3_000, 30
        conc_vs, conc_dur, cold_iters = 3_600.0, 1.0, 5
    else:
        srcs, vs, cached_iters, uncached_iters = 2_000, 43_200.0, 20_000, 50
        conc_vs, conc_dur, cold_iters = 7_200.0, 3.0, 10

    cache = bench_cache_leverage(srcs, vs, cached_iters, uncached_iters)
    rows.append((
        "query_cache_leverage",
        1e6 / cache["cached_qps"],               # us per cached query
        f"cached={cache['cached_qps']:,.0f}q/s "
        f"uncached={cache['uncached_qps']:,.0f}q/s "
        f"x{cache['speedup']:,.0f} over {cache['hot_segments']}segs",
    ))
    conc = bench_concurrency(srcs, conc_vs, conc_dur)
    for r in conc:
        rows.append((
            f"query_concurrency_{r['subscribers']}subs",
            1e6 / r["queries_s"],                # us per foreground query
            f"queries={r['queries_s']:,.0f}/s "
            f"watch_updates={r['watch_updates']} "
            f"threads_added={r['threads_added']} "
            f"stale={r['stale_rejected']}",
        ))
    cold = bench_cold_range(srcs // 2, vs / 4, cold_iters)
    rows.append((
        "query_cold_range",
        1e6 / cold["cold_qps"],                  # us per cold query
        f"cold={cold['cold_qps']:.1f}q/s "
        f"events/scan={cold['cold_events_per_scan']} "
        f"windows={cold['windows']} parity=ok",
    ))
    cold_col = bench_cold_range(srcs // 2, vs / 4, cold_iters,
                                columnar=True)
    rows.append((
        "query_cold_range_columnar",
        1e6 / cold_col["cold_qps"],              # us per cold query
        f"cold={cold_col['cold_qps']:.1f}q/s "
        f"(x{cold_col['cold_qps'] / cold['cold_qps']:.1f} vs json) "
        f"events/scan={cold_col['cold_events_per_scan']} "
        f"windows={cold_col['windows']} parity=ok",
    ))
    # machine-readable results land BEFORE the regression asserts so a
    # failing bar still leaves the numbers behind for inspection
    with open("BENCH_query.json", "w", encoding="utf-8") as fh:
        json.dump({"cache_leverage": cache, "concurrency": conc,
                   "cold_range": cold, "cold_range_columnar": cold_col,
                   "smoke": smoke}, fh, indent=2)
    # acceptance bars
    bar = CACHE_BAR_SMOKE if smoke else CACHE_BAR
    assert cache["speedup"] >= bar, (
        f"cache leverage below {bar}x: {cache['speedup']:.1f}x")
    for r in conc:
        assert r["stale_rejected"] == 0, (
            f"staleness bound violated at {r['subscribers']} subscribers")
        assert r["threads_added"] == 0, (
            f"{r['threads_added']} threads spawned for async subscribers")
        assert r["watch_updates"] > 0
    assert cold["parity_ok"] and cold_col["parity_ok"]
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    out: list = []
    main(out, smoke="--smoke" in sys.argv or "--tiny" in sys.argv)
    for name, us, derived in out:
        print(f"{name},{us:.0f},{derived}")
