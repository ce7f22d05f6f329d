"""Windowed-analytics + alerting benchmark (the paper's missing
downstream half): 20k sources x 1 virtual hour through the full pipeline
with the analytics stage mounted — events/sec into the window operator
and p50/p99 watermark-to-alert latency (virtual seconds from a window's
event-time close boundary to the alert firing) — plus the Pallas
``window_reduce`` kernel's batch-replay throughput over the same events.

  PYTHONPATH=src python -m benchmarks.bench_alerts          # full (20k x 1h)
  PYTHONPATH=src python -m benchmarks.bench_alerts --tiny   # CI smoke
"""
from __future__ import annotations

import sys
import time

import numpy as np

from repro.alerts import (
    RateOfChangeRule,
    ThresholdRule,
    WindowSpec,
    ZScoreRule,
)
from repro.core import AlertMixPipeline, PipelineConfig


def _percentile(xs, q):
    return float(np.percentile(np.asarray(xs), q)) if xs else float("nan")


def _run(num_sources: int, virtual_s: float, *, window_s: float = 60.0,
         seed: int = 0):
    cfg = PipelineConfig(
        num_sources=num_sources, feed_interval_s=300.0,
        workers=64 if num_sources >= 5000 else 8,
        queue_capacity=max(200_000, num_sources * 2),
        analytics=True, window_size_s=window_s,
        allowed_lateness_s=300.0, watermark_lag_s=30.0)
    rules = [
        ThresholdRule("volume", metric="count", op=">=", threshold=1.0),
        RateOfChangeRule("surge", metric="count", factor=1.5, min_value=2.0),
        ZScoreRule("anomaly", metric="count", z=2.5, min_history=5),
    ]
    p = AlertMixPipeline(cfg, seed=seed, analytics_rules=rules)
    t0 = time.time()
    p.run_for(virtual_s, dt=5.0,
              per_worker=max(8, num_sources // (cfg.workers * 20)))
    wall = time.time() - t0

    stage = p.analytics
    events = stage.operator.stats["events"]
    lat = [a.watermark_to_alert_s for a in p.alerts]
    return {
        "wall_s": wall,
        "events": events,
        "events_per_s": events / max(wall, 1e-9),
        "windows_closed": stage.closed_total,
        "alerts": len(p.alerts),
        "lat_p50_s": _percentile(lat, 50),
        "lat_p99_s": _percentile(lat, 99),
        "late_dropped": stage.operator.stats["late_dropped"],
    }


def _bench_kernel(n_events: int = 200_000, n_segments: int = 4096,
                  iters: int = 5, seed: int = 0):
    """Batch-replay path: one window_reduce launch over the event tensor."""
    import jax
    from repro.kernels import ops

    rng = np.random.default_rng(seed)
    vals = rng.normal(size=n_events).astype(np.float32)
    segs = rng.integers(0, n_segments, size=n_events).astype(np.int32)
    out = ops.window_reduce(vals, segs, n_segments)       # compile + warm
    jax.block_until_ready(out)
    t0 = time.time()
    for _ in range(iters):
        jax.block_until_ready(ops.window_reduce(vals, segs, n_segments))
    dt = (time.time() - t0) / iters
    return {"us_per_call": dt * 1e6, "events_per_s": n_events / dt}


def main(rows, *, tiny: bool = False):
    if tiny:
        r = _run(200, 600.0)
        k = _bench_kernel(n_events=20_000, n_segments=256, iters=2)
    else:
        r = _run(20_000, 3600.0)                          # 20k x 1 virtual hour
        k = _bench_kernel()
    rows.append((
        "alerts_e2e_tiny" if tiny else "alerts_e2e_20k_1h",
        1e6 * r["wall_s"],
        f"events/s={r['events_per_s']:,.0f} alerts={r['alerts']} "
        f"wm_to_alert_p50={r['lat_p50_s']:.1f}s "
        f"wm_to_alert_p99={r['lat_p99_s']:.1f}s "
        f"windows={r['windows_closed']} late={r['late_dropped']}",
    ))
    rows.append((
        "alerts_window_reduce_kernel",
        k["us_per_call"],
        f"events/s={k['events_per_s']:,.0f}",
    ))
    assert r["alerts"] > 0, "no alerts fired — rules or windows are broken"
    assert r["windows_closed"] > 0
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    out: list = []
    main(out, tiny="--tiny" in sys.argv)
    for name, us, derived in out:
        print(f"{name},{us:.0f},{derived}")
