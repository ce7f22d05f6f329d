"""Delivery-layer benchmark (repro.delivery): quantifies the unified
Sink stack the pipeline now emits through.

  fan-out width    docs/sec through BatchingSink -> FanOutSink as the
                   backend count grows 1 -> 8 (per-backend retry
                   envelopes included, IndexSink terminals)
  flush-batch      docs/sec vs BatchingSink.max_batch (1 = the retired
                   one-document-per-call pattern, larger = amortized)
  push latency     alert emit -> subscriber-callback latency p50/p99
                   (wall clock), plus e2e pipeline fan-out with an
                   injected-failure backend proving isolation numbers
  stalled backend  producer emit p50/p99 with one SLOW (not failing)
                   backend: serial fan-out serializes every emit behind
                   the stall; the dispatch plane (DispatchingSink
                   hand-off queues) keeps the producer's p99 within 2x
                   of the no-stall baseline while healthy backends
                   still receive every record

Writes machine-readable results to ``BENCH_delivery.json`` (CI uploads
it as an artifact so trajectories accumulate across commits).

  PYTHONPATH=src python -m benchmarks.bench_delivery          # full
  PYTHONPATH=src python -m benchmarks.bench_delivery --smoke  # CI smoke
"""
from __future__ import annotations

import json
import sys
import time

import numpy as np

from repro.core import AlertMixPipeline, PipelineConfig
from repro.core.sinks import IndexSink
from repro.delivery import (
    BatchingSink,
    CollectingSink,
    FanOutSink,
    RetryingSink,
    Sink,
    SubscriptionHub,
)


def _docs(n: int):
    return [(f"d{i}", {"title": f"doc {i} market news", "body": "x " * 8,
                       "published_at": float(i), "channel": "news"})
            for i in range(n)]


def _percentile(xs, q):
    return float(np.percentile(np.asarray(xs), q)) if len(xs) else float("nan")


class _Broken(Sink):
    def _write(self, batch):
        raise IOError("injected failure")


class _Stalled(Sink):
    """A slow (NOT failing) backend: every write blocks ``stall_s`` of
    wall time — a saturated index or a wedged socket."""

    def __init__(self, stall_s: float, name="stalled"):
        super().__init__(name)
        self.stall_s = stall_s
        self.records = []

    def _write(self, batch):
        time.sleep(self.stall_s)
        self.records.extend(batch)


def bench_stalled_backend(n_emits: int, *, batch: int = 16,
                          stall_s: float = 0.002) -> dict:
    """Producer-side emit latency through a 3-backend fan-out (two
    healthy CollectingSinks + one stalled), serial vs dispatched, plus
    a no-stall dispatched baseline.  The acceptance number: with the
    dispatch plane, one stalled backend must leave the producer's emit
    p99 within 2x of the no-stall baseline (serial mode serializes the
    whole loop behind the stall)."""
    docs = _docs(batch)

    def run(dispatch: bool, stalled: bool) -> dict:
        backends = [RetryingSink(CollectingSink("a"), name="a"),
                    RetryingSink(CollectingSink("b"), name="b")]
        if stalled:
            backends.append(
                RetryingSink(_Stalled(stall_s, name="slow"), name="slow"))
        fan = (FanOutSink.dispatching(backends, capacity=n_emits + 8,
                                      flush_deadline_s=60.0)
               if dispatch else FanOutSink(backends))
        lat = []
        for _ in range(n_emits):
            t0 = time.perf_counter()
            fan.emit(docs)
            lat.append(time.perf_counter() - t0)
        fan.flush()                        # drains dispatch queues
        healthy = [b.terminal for b in fan.backends
                   if b.terminal.name in ("a", "b")]
        complete = all(len(h.records) == n_emits * batch for h in healthy)
        fan.close()
        return {"p50_ms": _percentile(lat, 50) * 1e3,
                "p99_ms": _percentile(lat, 99) * 1e3,
                "healthy_complete": complete}

    baseline = run(dispatch=True, stalled=False)
    dispatched = run(dispatch=True, stalled=True)
    serial = run(dispatch=False, stalled=True)
    return {"baseline_nostall": baseline, "dispatch_stalled": dispatched,
            "serial_stalled": serial, "stall_ms": stall_s * 1e3,
            # the raw ratio (both sides are tens of microseconds, so it
            # jitters run to run; the acceptance assert uses an absolute
            # 1ms floor instead of this number)
            "isolation_factor_p99":
                dispatched["p99_ms"] / max(baseline["p99_ms"], 1e-9),
            "serial_penalty_factor_p99":
                serial["p99_ms"] / max(dispatched["p99_ms"], 1e-6)}


def bench_fanout_width(n_docs: int, widths=(1, 2, 4, 8)) -> dict:
    docs = _docs(n_docs)
    out = {}
    for w in widths:
        sink = BatchingSink(
            FanOutSink([RetryingSink(IndexSink()) for _ in range(w)]),
            max_batch=64)
        t0 = time.perf_counter()
        for i in range(0, n_docs, 16):           # worker-sized emits
            sink.emit(docs[i:i + 16])
        sink.flush()
        dt = time.perf_counter() - t0
        out[w] = n_docs / dt
    return out


def bench_batch_sweep(n_docs: int, batches=(1, 8, 64, 256)) -> dict:
    docs = _docs(n_docs)
    out = {}
    for bs in batches:
        inner = CollectingSink()
        sink = BatchingSink(FanOutSink([RetryingSink(inner)]), max_batch=bs)
        t0 = time.perf_counter()
        for d in docs:                           # one record per emit: the
            sink.emit([d])                       # old index() call pattern
        sink.flush()
        dt = time.perf_counter() - t0
        assert len(inner.records) == n_docs
        out[bs] = n_docs / dt
    return out


def bench_push_latency(n_alerts: int) -> dict:
    """emit -> subscriber-callback latency through the hub (wall clock)."""
    hub = SubscriptionHub()
    lat = []
    t0_box = [0.0]
    hub.subscribe(callback=lambda a: lat.append(time.perf_counter() - t0_box[0]))

    class _A:                                    # minimal alert-shaped record
        rule = "bench"

    a = _A()
    for _ in range(n_alerts):
        t0_box[0] = time.perf_counter()
        hub.emit([a])
    return {"p50_us": _percentile(lat, 50) * 1e6,
            "p99_us": _percentile(lat, 99) * 1e6,
            "pushed": len(lat)}


def bench_pipeline_fanout(num_sources: int, virtual_s: float) -> dict:
    """E2E: 3-backend fan-out (one injected failure) through the full
    pipeline; returns delivery counters as acceptance evidence."""
    healthy1, healthy2, broken = IndexSink(), IndexSink(), _Broken(name="down")
    p = AlertMixPipeline(
        PipelineConfig(num_sources=num_sources, feed_interval_s=120.0,
                       delivery_batch=16, delivery_retry_attempts=2),
        seed=0, sinks=[healthy1, healthy2, broken])
    t0 = time.perf_counter()
    m = p.run_for(virtual_s, dt=5.0)
    wall = time.perf_counter() - t0
    d = m.delivery["backends"]
    assert len(healthy1) == len(healthy2) == m.indexed_total
    assert d["down"]["dead_lettered"] == m.indexed_total
    return {"docs": m.indexed_total, "docs_per_s": m.indexed_total / wall,
            "dead_lettered": d["down"]["dead_lettered"],
            "retried": d["down"]["retried"]}


def main(rows, *, tiny: bool = False):
    n = 5_000 if tiny else 100_000
    widths = bench_fanout_width(n)
    rows.append((
        "delivery_fanout_width",
        1e6 * n / widths[max(widths)],
        " ".join(f"w{w}={r:,.0f}docs/s" for w, r in widths.items()),
    ))
    sweep = bench_batch_sweep(n)
    rows.append((
        "delivery_batch_sweep",
        1e6 * n / sweep[max(sweep)],
        " ".join(f"b{b}={r:,.0f}docs/s" for b, r in sweep.items()),
    ))
    push = bench_push_latency(1_000 if tiny else 50_000)
    rows.append((
        "delivery_alert_push",
        push["p50_us"],
        f"push_p50={push['p50_us']:.1f}us push_p99={push['p99_us']:.1f}us "
        f"n={push['pushed']}",
    ))
    e2e = bench_pipeline_fanout(200 if tiny else 5_000,
                                600.0 if tiny else 3600.0)
    rows.append((
        "delivery_pipeline_3way_fanout",
        1e6 / max(e2e["docs_per_s"], 1e-9),      # us per delivered doc
        f"docs={e2e['docs']} docs/s={e2e['docs_per_s']:,.0f} "
        f"dead_lettered={e2e['dead_lettered']} retried={e2e['retried']}",
    ))
    stall = bench_stalled_backend(80 if tiny else 400)
    rows.append((
        "delivery_stalled_backend_isolation",
        stall["dispatch_stalled"]["p99_ms"] * 1e3,   # us producer emit p99
        f"dispatch_p99={stall['dispatch_stalled']['p99_ms']:.3f}ms "
        f"baseline_p99={stall['baseline_nostall']['p99_ms']:.3f}ms "
        f"serial_p99={stall['serial_stalled']['p99_ms']:.3f}ms "
        f"(x{stall['serial_penalty_factor_p99']:.0f} worse) "
        f"isolation=x{stall['isolation_factor_p99']:.2f}",
    ))
    # JSON first: a failing regression assert must still leave the
    # evidence on disk for CI's always() artifact upload
    with open("BENCH_delivery.json", "w", encoding="utf-8") as fh:
        json.dump({"fanout_width_docs_s": {str(k): v
                                           for k, v in widths.items()},
                   "batch_sweep_docs_s": {str(k): v
                                          for k, v in sweep.items()},
                   "alert_push_latency": push,
                   "pipeline_3way_fanout": e2e,
                   "stalled_backend_isolation": stall,
                   "smoke": tiny}, fh, indent=2)

    # batching must beat the single-record pattern; fan-out must scale
    # sublinearly in cost (width 8 no worse than 12x slower than width 1)
    assert sweep[max(sweep)] > sweep[1] * 1.2, "batching amortization regressed"
    assert widths[8] * 12 > widths[1], "fan-out overhead regressed"
    assert e2e["docs"] > 0 and e2e["dead_lettered"] == e2e["docs"]
    # flow-control acceptance: healthy backends stay complete, and (full
    # run only — CI smoke on a shared 2-core runner just reports) one
    # stalled backend leaves the producer's emit p99 within 2x of the
    # no-stall baseline — with a 1ms absolute floor so the check binds on
    # real stalls, not on microsecond enqueue jitter — where serial
    # fan-out pays the stall on EVERY emit
    assert stall["dispatch_stalled"]["healthy_complete"]
    assert stall["serial_stalled"]["healthy_complete"]
    if not tiny:
        bound_ms = max(2.0 * stall["baseline_nostall"]["p99_ms"], 1.0)
        assert stall["dispatch_stalled"]["p99_ms"] <= bound_ms, \
            f"stalled-backend isolation regressed: {stall}"
        assert stall["serial_stalled"]["p99_ms"] >= stall["stall_ms"], \
            "serial baseline lost its stall — scenario broken"
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    out: list = []
    main(out, tiny="--tiny" in sys.argv or "--smoke" in sys.argv)
    for name, us, derived in out:
        print(f"{name},{us:.0f},{derived}")
