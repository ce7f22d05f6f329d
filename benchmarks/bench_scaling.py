"""Source-count scaling (the paper's flexibility claim: add/remove
sources on an ongoing basis) + resizer ablation: throughput with the
OptimalSizeExploringResizer vs fixed pool sizes."""
from __future__ import annotations

import time

from repro.core import AlertMixPipeline, PipelineConfig


def _throughput(num_sources, *, resizer=True, workers=16, virtual_s=1800.0):
    p = AlertMixPipeline(PipelineConfig(
        num_sources=num_sources, feed_interval_s=300.0, workers=workers,
        resizer=resizer, queue_capacity=max(100_000, 2 * num_sources)), seed=1)
    t0 = time.time()
    m = p.run_for(virtual_s, dt=5.0, per_worker=16)
    wall = time.time() - t0
    # steady-state rate: second half only (the resizer ramps up first)
    half = virtual_s / 2
    done = sum(n for t, n in m.received if t >= half)
    return done / half, wall, p.pool.size


def main(rows):
    t0 = time.time()
    scale = []
    for n in (1_000, 10_000, 50_000):
        thr, wall, _ = _throughput(n)
        scale.append((n, thr))
    rows.append((
        "alertmix_scaling",
        1e6 * (time.time() - t0),
        " ".join(f"{n}->{t:.1f}msg/s" for n, t in scale),
    ))
    # throughput must scale ~linearly with sources (they're on schedules)
    assert scale[-1][1] > scale[0][1] * 20

    t0 = time.time()
    thr_rz, _, end_size = _throughput(20_000, resizer=True, workers=4)
    thr_fixed_small, _, _ = _throughput(20_000, resizer=False, workers=4)
    rows.append((
        "alertmix_resizer_ablation",
        1e6 * (time.time() - t0),
        f"auto={thr_rz:.1f}msg/s (end_size={end_size}) "
        f"fixed4={thr_fixed_small:.1f}msg/s",
    ))
    # the resizer must at least keep up with schedule demand
    assert thr_rz >= 20_000 / 300.0 * 0.95
    return rows


if __name__ == "__main__":
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    out = []
    main(out)
    for name, us, derived in out:
        print(f"{name},{us:.0f},{derived}")
