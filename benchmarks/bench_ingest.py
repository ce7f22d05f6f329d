"""Ingestion-plane benchmark (repro.ingest): quantifies the sharded
registry and the connector fan-in the pipeline now rides.

  pick/mark throughput  pick_due + mark_processed cycles at 1/8/64
                        shards x 10k/200k sources, single-threaded and
                        under 4-thread contention (the per-shard-worker
                        deployment shape).  shards=1 is the seed's
                        single-lock StreamRegistry, the baseline the
                        acceptance criterion compares against.
  scheduler tick        Scheduler.maybe_tick latency p50/p99 over a
                        populated registry (requeue + pick + distribute)
  connector fan-in      docs/sec through JsonlTailConnector /
                        EventLogConnector / PushConnector push+drain
  back-pressure         upstream fetch-rate reduction when connectors
                        send backoff hints (RateLimitedConnector /
                        FetchResult.backoff_hint_s folded into
                        next_due): fetches with vs without the limiter
                        over the same virtual hour

Writes machine-readable results to ``BENCH_ingest.json`` (CI uploads it
as an artifact so trajectories accumulate across commits).

  PYTHONPATH=src python -m benchmarks.bench_ingest            # full
  PYTHONPATH=src python -m benchmarks.bench_ingest --smoke    # CI smoke
"""
from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import threading
import time

import numpy as np

from repro.core import StreamRegistry
from repro.core.dead_letters import DeadLettersListener
from repro.core.queues import BoundedPriorityQueue
from repro.core.scheduler import ChannelDistributor, Scheduler
from repro.ingest import (
    Cursor,
    EventLogConnector,
    JsonlTailConnector,
    PushConnector,
    RateLimitedConnector,
    ShardedStreamRegistry,
)


def _build_registry(shards: int, n_sources: int, *, interval_s: float = 0.0,
                    spread_s: float = 0.0):
    reg = (StreamRegistry() if shards == 1
           else ShardedStreamRegistry(shards=shards))
    for i in range(n_sources):
        first = (i / n_sources) * spread_s if spread_s else 0.0
        reg.add_source("news", first_due=first, interval_s=interval_s)
    return reg


def bench_pick_mark(shards: int, n_sources: int, threads: int,
                    duration_s: float) -> float:
    """Sources on a zero interval are always due: every thread loops
    pick_due(limit=256) -> mark_processed, the scheduler/updater hot
    path.  Returns sustained cycles/sec across all threads."""
    reg = _build_registry(shards, n_sources)
    ops = [0] * threads
    stop = time.perf_counter() + duration_s

    def worker(t: int) -> None:
        now = 0.0
        while time.perf_counter() < stop:
            batch = reg.pick_due(now, limit=256)
            for s in batch:
                reg.mark_processed(s.sid, now)
            ops[t] += len(batch)
            now += 1.0

    ts = [threading.Thread(target=worker, args=(t,)) for t in range(threads)]
    t0 = time.perf_counter()
    for x in ts:
        x.start()
    for x in ts:
        x.join()
    return sum(ops) / (time.perf_counter() - t0)


def bench_scheduler_tick(shards: int, n_sources: int,
                         n_ticks: int) -> dict:
    """p50/p99 maybe_tick latency: requeue_expired + pick_due +
    distribute over a registry on the paper's 5-minute cadence."""
    reg = _build_registry(shards, n_sources, interval_s=300.0,
                          spread_s=300.0)
    dl = DeadLettersListener()
    dist = ChannelDistributor(dead_letters=dl)
    dist.register_channel("news",
                          BoundedPriorityQueue(n_sources + 1, dead_letters=dl),
                          BoundedPriorityQueue(n_sources + 1, dead_letters=dl))
    sched = Scheduler(reg, dist, interval_s=5.0, pick_limit=n_sources)
    lat = []
    now = 0.0
    for _ in range(n_ticks):
        t0 = time.perf_counter()
        sched.maybe_tick(now)
        lat.append(time.perf_counter() - t0)
        # complete the cycle outside the timed region
        for msg in dist.main_queues["news"].poll_batch(n_sources):
            reg.mark_processed(msg.sid, now)
        now += 5.0
    us = np.asarray(lat) * 1e6
    return {"tick_p50_us": float(np.percentile(us, 50)),
            "tick_p99_us": float(np.percentile(us, 99)),
            "picked_total": sched.picked_total}


def bench_connector_fan_in(n_docs: int) -> dict:
    """Docs/sec into FeedItems through each shipped connector."""
    d = tempfile.mkdtemp(prefix="bench_ingest_")
    try:
        src = StreamRegistry()
        src.add_source("news")
        source = src.get(0)

        path = os.path.join(d, "feed.jsonl")
        with open(path, "w", encoding="utf-8") as fh:
            for i in range(n_docs):
                fh.write(json.dumps({"guid": f"g{i}", "title": f"doc {i}",
                                     "body": "x " * 16,
                                     "published_at": float(i)}) + "\n")
        conn = JsonlTailConnector(path, max_bytes=1 << 30)
        t0 = time.perf_counter()
        res = conn.fetch(source, Cursor(), now=0.0)
        jsonl_rate = len(res.items) / (time.perf_counter() - t0)
        assert len(res.items) == n_docs

        from repro.store import EventLog
        log = EventLog(os.path.join(d, "log"), segment_bytes=16 << 20)
        log.append([{"id": f"g{i}", "doc": {"title": f"doc {i}",
                                            "body": "x " * 16,
                                            "published_at": float(i)}}
                    for i in range(n_docs)])
        lconn = EventLogConnector(log, max_records=n_docs)
        t0 = time.perf_counter()
        res = lconn.fetch(source, Cursor(), now=0.0)
        log_rate = len(res.items) / (time.perf_counter() - t0)
        assert len(res.items) == n_docs
        log.close()

        pconn = PushConnector(capacity=n_docs + 1)
        docs = [{"guid": f"g{i}", "title": "t", "body": "b"}
                for i in range(n_docs)]
        t0 = time.perf_counter()
        for i in range(0, n_docs, 256):           # webhook-sized posts
            pconn.push(0, docs[i:i + 256])
        res = pconn.fetch(source, Cursor(), now=0.0)
        push_rate = len(res.items) / (time.perf_counter() - t0)
        assert len(res.items) == n_docs

        return {"jsonl_docs_s": jsonl_rate, "eventlog_docs_s": log_rate,
                "push_docs_s": push_rate, "docs": n_docs}
    finally:
        shutil.rmtree(d, ignore_errors=True)


def bench_backpressure(n_sources: int, virtual_s: float,
                       min_interval_s: float = 600.0) -> dict:
    """Upstream fetch-rate with and without connector back-pressure:
    the same hot sources (60s interval) polled raw vs behind a
    RateLimitedConnector whose backoff hints the registry folds into
    next_due.  The ratio is upstream load shed by flow control."""
    from repro.core import AlertMixPipeline, PipelineConfig

    class _Counting:
        name = "hot"

        def __init__(self):
            self.fetches = 0

        def fetch(self, source, cursor, now):
            self.fetches += 1
            from repro.core.sources import NOT_MODIFIED, FetchResult
            return FetchResult(NOT_MODIFIED, etag="e",
                               position=cursor.position)

    def run(limited: bool) -> int:
        conn = _Counting()
        p = AlertMixPipeline(PipelineConfig(num_sources=0,
                                            pick_interval_s=5.0), seed=0)
        name = p.register_connector(
            RateLimitedConnector(conn, min_interval_s=min_interval_s)
            if limited else conn, "hot")
        for _ in range(n_sources):
            p.add_source("news", interval_s=60.0, connector=name)
        p.run_for(virtual_s, dt=5.0)
        return conn.fetches

    raw = run(limited=False)
    limited = run(limited=True)
    return {"fetches_raw": raw, "fetches_limited": limited,
            "reduction_factor": raw / max(1, limited),
            "sources": n_sources, "virtual_s": virtual_s,
            "min_interval_s": min_interval_s}


def main(rows, *, smoke: bool = False):
    shard_counts = (1, 8, 64)
    source_counts = (5_000,) if smoke else (10_000, 200_000)
    duration = 0.15 if smoke else 0.5
    pick_mark: dict = {}
    for n in source_counts:
        for shards in shard_counts:
            for threads in (1, 4):
                rate = bench_pick_mark(shards, n, threads, duration)
                pick_mark[f"s{shards}_n{n}_t{threads}"] = rate
    n_top = source_counts[-1]
    base = pick_mark[f"s1_n{n_top}_t4"]
    speedup8 = pick_mark[f"s8_n{n_top}_t4"] / base
    speedup64 = pick_mark[f"s64_n{n_top}_t4"] / base
    rows.append((
        "ingest_pick_mark",
        1e6 / pick_mark[f"s8_n{n_top}_t4"],       # us per picked stream
        f"n={n_top} t4: 1shard={base:,.0f}/s "
        f"8shards={pick_mark[f's8_n{n_top}_t4']:,.0f}/s (x{speedup8:.1f}) "
        f"64shards=x{speedup64:.1f}",
    ))
    # the acceptance floor: sharding must beat the single lock under
    # contention at the largest source count.  Timing-based, so only
    # enforced on the full run — the 0.15s-per-config CI smoke on a
    # 2-core shared runner just reports the number
    if not smoke:
        assert speedup8 > 1.2, f"8-shard speedup {speedup8:.2f} <= 1.2"

    tick = {f"s{shards}": bench_scheduler_tick(
                shards, n_top, n_ticks=20 if smoke else 100)
            for shards in (1, 8)}
    rows.append((
        "ingest_scheduler_tick",
        tick["s8"]["tick_p50_us"],
        f"n={n_top} p50={tick['s8']['tick_p50_us']:.0f}us "
        f"p99={tick['s8']['tick_p99_us']:.0f}us "
        f"(1shard p99={tick['s1']['tick_p99_us']:.0f}us)",
    ))

    fan_in = bench_connector_fan_in(2_000 if smoke else 50_000)
    rows.append((
        "ingest_connector_fan_in",
        1e6 / fan_in["jsonl_docs_s"],             # us per tailed doc
        f"jsonl={fan_in['jsonl_docs_s']:,.0f}doc/s "
        f"eventlog={fan_in['eventlog_docs_s']:,.0f}doc/s "
        f"push={fan_in['push_docs_s']:,.0f}doc/s",
    ))
    assert all(v > 0 for v in
               (fan_in["jsonl_docs_s"], fan_in["eventlog_docs_s"],
                fan_in["push_docs_s"]))

    bp = bench_backpressure(50 if smoke else 500, 3600.0)
    rows.append((
        "ingest_backpressure",
        bp["reduction_factor"],
        f"fetches/h raw={bp['fetches_raw']} "
        f"limited={bp['fetches_limited']} "
        f"(x{bp['reduction_factor']:.1f} load shed, "
        f"min_interval={bp['min_interval_s']:.0f}s)",
    ))
    # JSON before the assert: a failing run must still leave evidence
    # for CI's always() artifact upload
    with open("BENCH_ingest.json", "w", encoding="utf-8") as fh:
        json.dump({"pick_mark_ops_s": pick_mark,
                   "speedup_8_shards_vs_single_lock": speedup8,
                   "speedup_64_shards_vs_single_lock": speedup64,
                   "scheduler_tick": tick,
                   "connector_fan_in": fan_in,
                   "backpressure": bp,
                   "sources_top": n_top, "smoke": smoke}, fh, indent=2)

    # deterministic (virtual clock): a 600s limiter on 60s sources must
    # shed most of the upstream load
    assert bp["reduction_factor"] > 5.0, bp
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    out: list = []
    main(out, smoke="--smoke" in sys.argv or "--tiny" in sys.argv)
    for name, us, derived in out:
        print(f"{name},{us:.0f},{derived}")
