# One function per paper table. Print ``name,us_per_call,derived`` CSV.
"""Benchmark harness.

  bench_alertmix  — Fig. 4: 200k-feed ingestion, drain vs ingest, peak rate
  bench_ingest    — ingestion plane: sharded-registry pick/mark
                    throughput (1/8/64 shards, 10k/200k sources),
                    scheduler tick p50/p99, connector fan-in rates
                    (writes BENCH_ingest.json)
  bench_alerts    — windowed analytics: events/sec + watermark-to-alert
                    latency (p50/p99) + window_reduce kernel throughput
  bench_delivery  — delivery layer: docs/sec vs fan-out width, flush-
                    batch sweep, alert push latency p50/p99
  bench_store     — durability plane: event-log append/scan MB/s, batch
                    replay vs live-path events/sec with per-stage
                    profile shares, recovery-to-drain latency
                    (writes BENCH_store.json)
  bench_obs       — observability plane: tracing overhead at sample
                    rate 1.0 vs off and always-on latency/SLO-plane
                    overhead at rate 0 (both <=10% asserted),
                    exposition scrape cost, JSONL span-export rate
                    (writes BENCH_obs.json + a sample trace in
                    BENCH_obs_trace.jsonl)
  bench_query     — query/serving plane: cached vs recomputed query
                    throughput (>=100x asserted), queries/s under
                    1/16/64 async subscribers at the staleness bound,
                    cold-range replay parity (writes BENCH_query.json)
  bench_chaos     — chaos plane: full fault-injection scenario matrix
                    (every catalog scenario x 2 seeds) through the real
                    five-plane stack; reports faults absorbed, virtual-
                    vs-wall speedup, worst recovery latency; red runs
                    persist the failing seed (writes BENCH_chaos.json
                    + CHAOS_FAILURE.json on breach)
  bench_scaling   — source-count scaling + resizer ablation
  bench_serving   — continuous vs static batching (FeedRouter admission)
  bench_train     — CPU train-step throughput per model family
  bench_roofline  — §Roofline table from the dry-run records

Every full run also appends its flattened scalars to
``BENCH_history.jsonl`` — ``python -m benchmarks.compare`` diffs the
newest entry against the previous one (the perf-trajectory gate).

Run everything:  PYTHONPATH=src python -m benchmarks.run
One benchmark:   PYTHONPATH=src python -m benchmarks.bench_alertmix
"""
from __future__ import annotations

import sys
import traceback


def main() -> None:
    from benchmarks import (
        bench_alertmix,
        bench_alerts,
        bench_chaos,
        bench_delivery,
        bench_ingest,
        bench_obs,
        bench_query,
        bench_roofline,
        bench_scaling,
        bench_serving,
        bench_store,
        bench_train,
    )

    rows: list = []
    failures = 0
    for mod in (bench_alertmix, bench_ingest, bench_alerts, bench_delivery,
                bench_store, bench_obs, bench_query, bench_chaos,
                bench_scaling, bench_serving,
                bench_train, bench_roofline):
        try:
            mod.main(rows)
        except Exception:
            failures += 1
            print(f"BENCH FAILED: {mod.__name__}", file=sys.stderr)
            traceback.print_exc()
    print("name,us_per_call,derived")
    for name, us, derived in rows:
        print(f"{name},{us:.0f},{derived}")
    # perf trajectory: one history line per harness run, appended even
    # when a bench failed (partial rows still anchor the next diff)
    from benchmarks.compare import append_entry
    append_entry({name: us for name, us, _ in rows})
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
