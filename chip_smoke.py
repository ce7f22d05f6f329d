#!/usr/bin/env python3
"""Bring-up smoke: the streaming pipeline's kernel path on one TPU chip.

    python3 chip_smoke.py

Drives one ``AlertMixPipeline`` through its public entry points, in one
process, at the size of the windowed-analytics benchmark
(``benchmarks/bench_alerts.py``): 20,000 simulated feeds refreshed every
5 minutes, 60 s tumbling windows with 300 s allowed lateness, the three
``bench_alerts`` rules, a columnar store, and the query plane holding 16
windows per key so that older ranges are answered from the log.  The
pipeline runs one virtual hour (``bench_alerts``' full length) in steps
of 5 s.  At 20,000 feeds its default worker pool falls behind, so
fetched events arrive past the lateness bound and reach the dead-letter
journal, which every ``flush_delivery`` drains through the kernel.

Every route that reaches the device is checked against a plain NumPy /
Python reference built from the same events, sharing no code with the
kernel path:

  (a) late_drain   the late-event drain at each ``flush_delivery``
                   (every 5 virtual minutes), against a float64 group-by
                   of the events the live window operator dead-lettered;
  (c) cold_query   ``AggQuery(agg="min")`` over ``[0, floor)`` for each
                   channel, against a pure-Python fold of the event log;
  (b) log_replay   ``ReplayEngine.replay_columns`` over the whole
                   columnar log (sealed blocks and JSON tail), against a
                   float64 group-by of its lanes
                   (run after (c): replayed windows also merge into the
                   query plane's hot store);
  (d) direct       one ``ops.window_reduce`` launch at 200,000 events x
                   4,096 segments, and one sliding-window (300 s every
                   60 s) ``pack_columns`` batch over the log's lanes, both
                   with seeded normal values, against a float64 group-by.

Counts and max must match exactly.  Sum and sum of squares must fall
within the float32 accumulation bound for the slot's n events in any
summation order (Higham, Accuracy and Stability of Numerical Algorithms,
2nd ed., section 4.2), with u = 2**-24 and g(k) = k*u / (1 - k*u):

    |sum - ref|   <= g(n-1) * sum|v|   + n * 2**-126 + 2n * 2**-53 * sum|v|
    |sumsq - ref| <= g(n)   * sum(v^2) + n * 2**-126 + 2n * 2**-53 * sum(v^2)

The second term covers the TPU's flush of subnormal results and the third
the float64 reference's own rounding.  The kernel's non-member lanes add
exact zeros, so only the slot's own n terms round.

Every launch must run with ``interpret=False``, and the lowered
``window_reduce_fwd`` for the 200,000 x 4,096 shape must contain
``tpu_custom_call``.  Earlier lines of standard output are JSON objects:
one per phase, then a summary with the device, the pipeline's counts,
launches per route, compilations and their seconds, wall seconds per
phase, the worst errors and ``peak_bytes_in_use``.  The last line is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

and is printed only when every phase passed.  On any other platform, any
mismatch or any exception the script exits non-zero without it.
"""
from __future__ import annotations

import json
import math
import os
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, Hashable, Iterable, List, Sequence, Tuple

import numpy as np

SRC = Path(__file__).resolve().parent / "src"

NUM_SOURCES = 20_000
VIRTUAL_S = 3600.0          # bench_alerts: 20k feeds x 1 virtual hour
CHUNK_S = 300.0             # one flush_delivery (late drain) per chunk
DT_S = 5.0
WINDOW_S = 60.0
KERNEL_EVENTS = 200_000     # bench_alerts' kernel size
KERNEL_SEGMENTS = 4096
SLIDING_SIZE_S = 300.0
SLIDING_SLIDE_S = 60.0
SEED = 0
ROUTES = ("drain", "query", "replay", "direct")   # repro.obs.launches

_U = 2.0 ** -24             # float32 unit roundoff
_U64 = 2.0 ** -53           # float64 unit roundoff
_TINY = 2.0 ** -126         # smallest normal float32

Slot = Tuple[Hashable, float]          # (key, window start)
Lanes4 = Tuple[float, float, float, float]   # count, sum, sumsq, max


class SmokeFailure(RuntimeError):
    """A phase did not match its reference, or a precondition failed."""


def _check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def _emit(obj: dict) -> None:
    print(json.dumps(obj, sort_keys=True), flush=True)


# ---- references ------------------------------------------------------------

def reference_reduce(keys: Sequence[Hashable], starts: np.ndarray,
                     values: np.ndarray) -> Dict[Slot, tuple]:
    """float64 group-by over (key, window start) ->
    (count, sum, sumsq, max, sum|v|), from values rounded to float32 as
    the kernel receives them."""
    codes_of: Dict[Hashable, int] = {}
    codes = np.fromiter((codes_of.setdefault(k, len(codes_of)) for k in keys),
                        dtype=np.int64, count=len(keys))
    vocab = list(codes_of)
    starts = np.asarray(starts, np.float64)
    v = np.asarray(values, np.float32).astype(np.float64)
    if v.size == 0:
        return {}
    order = np.lexsort((starts, codes))
    c, s, v = codes[order], starts[order], v[order]
    cut = np.flatnonzero((np.diff(c) != 0) | (np.diff(s) != 0)) + 1
    first = np.concatenate([[0], cut])
    counts = np.diff(np.concatenate([first, [c.size]]))
    sums = np.add.reduceat(v, first)
    sumsq = np.add.reduceat(v * v, first)
    maxes = np.maximum.reduceat(v, first)
    sumabs = np.add.reduceat(np.abs(v), first)
    return {(vocab[c[i]], float(s[i])):
            (int(counts[j]), float(sums[j]), float(sumsq[j]),
             float(maxes[j]), float(sumabs[j]))
            for j, i in enumerate(first)}


def tumbling_starts(ts: np.ndarray, size_s: float) -> np.ndarray:
    return np.floor(np.asarray(ts, np.float64) / size_s) * size_s


def sliding_expand(ts: np.ndarray, size_s: float, slide_s: float):
    """Index of the event and start of each sliding window covering it:
    starts k*slide with t - size < k*slide <= t."""
    ts = np.asarray(ts, np.float64)
    last = np.floor(ts / slide_s) * slide_s
    idx: List[np.ndarray] = []
    starts: List[np.ndarray] = []
    for j in range(int(math.ceil(size_s / slide_s)) + 1):
        s = last - j * slide_s
        m = s > ts - size_s
        idx.append(np.flatnonzero(m))
        starts.append(s[m])
    return np.concatenate(idx), np.concatenate(starts)


def _gamma(k: int) -> float:
    return k * _U / (1.0 - k * _U)


def compare(phase: str, got: Dict[Slot, Lanes4],
            ref: Dict[Slot, tuple]) -> dict:
    """Exact count/max, bounded sum/sumsq; -> worst errors seen."""
    missing = sorted(set(ref) - set(got), key=repr)[:3]
    extra = sorted(set(got) - set(ref), key=repr)[:3]
    _check(not missing and not extra,
           f"{phase}: slots differ from the reference: {len(got)} vs "
           f"{len(ref)}, missing {missing}, extra {extra}")
    worst = {"slots": len(ref), "sum_abs_err": 0.0, "sumsq_abs_err": 0.0,
             "sum_err_over_bound": 0.0, "sumsq_err_over_bound": 0.0}
    for slot, (rc, rs, rsq, rmx, rabs) in ref.items():
        cnt, sm, sq, mx = got[slot]
        _check(cnt == rc, f"{phase}: count {cnt} != {rc} at {slot}")
        _check(mx == rmx, f"{phase}: max {mx!r} != {rmx!r} at {slot}")
        b_sum = (_gamma(rc - 1) * rabs + rc * _TINY
                 + 2 * rc * _U64 * rabs)
        b_sq = _gamma(rc) * rsq + rc * _TINY + 2 * rc * _U64 * rsq
        e_sum, e_sq = abs(sm - rs), abs(sq - rsq)
        _check(e_sum <= b_sum,
               f"{phase}: sum {sm!r} vs {rs!r} at {slot}: error {e_sum} "
               f"> bound {b_sum}")
        _check(e_sq <= b_sq,
               f"{phase}: sumsq {sq!r} vs {rsq!r} at {slot}: error {e_sq} "
               f"> bound {b_sq}")
        worst["sum_abs_err"] = max(worst["sum_abs_err"], e_sum)
        worst["sumsq_abs_err"] = max(worst["sumsq_abs_err"], e_sq)
        if b_sum > 0:
            worst["sum_err_over_bound"] = max(worst["sum_err_over_bound"],
                                              e_sum / b_sum)
        if b_sq > 0:
            worst["sumsq_err_over_bound"] = max(
                worst["sumsq_err_over_bound"], e_sq / b_sq)
    return worst


def _merge_worst(parts: Iterable[dict]) -> dict:
    out = {"slots": 0, "sum_abs_err": 0.0, "sumsq_abs_err": 0.0,
           "sum_err_over_bound": 0.0, "sumsq_err_over_bound": 0.0}
    for w in parts:
        out["slots"] += w["slots"]
        for k in out:
            if k != "slots":
                out[k] = max(out[k], w[k])
    return out


def _agg_lanes(aggs) -> Dict[Slot, Lanes4]:
    out: Dict[Slot, Lanes4] = {}
    for a in aggs:
        slot = (a.key, a.window_start)
        _check(slot not in out, f"duplicate aggregate for {slot}")
        _check(a.window_end - a.window_start == WINDOW_S,
               f"window {slot} ends at {a.window_end}")
        out[slot] = (a.count, a.sum, a.sumsq, a.max)
    return out


# ---- instrumentation -------------------------------------------------------

def launch_counts() -> Dict[str, Dict[str, int]]:
    """The program's own ``window_reduce`` launch counters, per route
    (``repro.obs.launches``: replay, drain, query, direct)."""
    from repro.obs.launches import kernel_launches

    return kernel_launches().get("window_reduce", {})


def launches_since(before: Dict[str, Dict[str, int]]
                   ) -> Dict[str, Dict[str, int]]:
    """Per route, the counters' growth since ``before`` (routes that did
    not launch are left out)."""
    out = {}
    for route, now in launch_counts().items():
        was = before.get(route, {})
        delta = {k: v - was.get(k, 0) for k, v in now.items()}
        if delta["launches"]:
            out[route] = delta
    return out


class CompileMonitor:
    """Counts XLA compilations (each executable built or read from the
    persistent cache) and their seconds, through ``jax.monitoring``."""

    COMPILE = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.compiles = 0
        self.compile_s = 0.0
        self.kernel_compiles = 0
        self.kernel_compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0

    def install(self) -> None:
        import jax.monitoring

        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, duration: float, **kw) -> None:
        if event != self.COMPILE:
            return
        self.compiles += 1
        self.compile_s += duration
        if "window_reduce_fwd" in str(kw.get("fun_name")):
            self.kernel_compiles += 1
            self.kernel_compile_s += duration

    def _event(self, event: str, **kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self) -> dict:
        return dict(vars(self))


# ---- the pipeline and its phases -------------------------------------------

def build_pipeline(store_dir: str, *, num_sources: int = NUM_SOURCES,
                   seed: int = SEED, **overrides):
    from repro.alerts import RateOfChangeRule, ThresholdRule, ZScoreRule
    from repro.core import AlertMixPipeline, PipelineConfig

    cfg = dict(num_sources=num_sources, feed_interval_s=300.0,
               analytics=True, query=True, window_size_s=WINDOW_S,
               allowed_lateness_s=300.0, store_dir=store_dir,
               store_columnar=True, query_max_windows_per_key=16)
    cfg.update(overrides)
    rules = [   # benchmarks/bench_alerts.py
        ThresholdRule("volume", metric="count", op=">=", threshold=1.0),
        RateOfChangeRule("surge", metric="count", factor=1.5, min_value=2.0),
        ZScoreRule("anomaly", metric="count", z=2.5, min_history=5),
    ]
    return AlertMixPipeline(PipelineConfig(**cfg), seed=seed,
                            analytics_rules=rules)


def run_with_late_drains(p, *,
                         virtual_s: float = VIRTUAL_S,
                         chunk_s: float = CHUNK_S, **run_kw) -> dict:
    """(a): run the pipeline in chunks; each ``run_for`` ends in
    ``flush_delivery``, which drains the late-event journal through the
    kernel.  Each drain is checked against the events dead-lettered as
    ``late_event`` since the previous one."""
    late: List[dict] = []
    p.dead_letters.subscribe(
        lambda reason, msg: late.append(msg) if reason == "late_event"
        else None)
    replay = p.store.replay
    drained: List[list] = []
    inner = replay.replay_events

    def recording(events, **kw):
        aggs, fired = inner(events, **kw)
        drained.append(aggs)
        return aggs, fired

    replay.replay_events = recording
    worst, drains, late_total = [], 0, 0
    try:
        while p.now < virtual_s:
            p.run_for(min(chunk_s, virtual_s - p.now), dt=DT_S, **run_kw)
            got = [a for aggs in drained for a in aggs]
            ref = reference_reduce(
                [m["key"] for m in late],
                tumbling_starts([m["event_time"] for m in late], WINDOW_S),
                np.array([m["value"] for m in late], np.float64))
            worst.append(compare("late_drain", _agg_lanes(got), ref))
            drains += len(drained)
            late_total += len(late)
            late.clear()
            drained.clear()
    finally:
        del replay.replay_events
    _check(late_total > 0, "no event reached the late-event journal")
    return {"phase": "late_drain", "drains": drains,
            "late_events": late_total, **_merge_worst(worst)}


def check_cold_query(p) -> dict:
    """(c): ``AggQuery(agg="min")`` over ``[0, floor)`` per channel, against
    a pure-Python fold of every document in the event log."""
    from repro.query import AggQuery

    floor = p.query.status()["floor"]
    _check(floor > 0.0, f"retention never evicted a window (floor {floor})")
    ref: Dict[Tuple[str, float], List[float]] = {}
    for _off, payload in p.store.log.scan():
        doc = payload["doc"]
        if "key" in doc:
            continue
        start = math.floor(float(doc["published_at"]) / WINDOW_S) * WINDOW_S
        if start + WINDOW_S <= 0.0 or start >= floor:
            continue
        v = float(doc.get("value", 1.0))
        cur = ref.setdefault((doc["channel"], start), [0, math.inf])
        cur[0] += 1
        cur[1] = min(cur[1], v)
    scans0 = p.query.status()["cold_scans"]
    points = 0
    for channel in p.channels():
        res = p.query.query(AggQuery(channel=channel, start=0.0,
                                     end=floor, agg="min"),
                            use_cache=False)
        got = {(pt["key"], pt["start"]): [pt["count"], pt["value"]]
               for pt in res.points}
        want = {k: v for k, v in ref.items() if k[0] == channel}
        diff = sorted(k for k in set(got) | set(want)
                      if got.get(k) != want.get(k))
        _check(not diff,
               f"cold_query {channel}: {len(diff)} of {len(want)} "
               f"windows differ from the log fold, e.g. "
               f"{[(k, got.get(k), want.get(k)) for k in diff[:3]]}")
        points += len(got)
    scans = p.query.status()["cold_scans"] - scans0
    _check(scans > 0, "no query reached the cold path")
    return {"phase": "cold_query", "floor": floor, "windows": points,
            "cold_scans": scans}


def check_log_replay(p) -> dict:
    """(b): ``replay_columns`` over the whole columnar log."""
    _check(p.store_stats()["columnar"]["sealed_columnar_segments"] > 0,
           "no sealed columnar segment to replay")
    lanes = p.store.log.scan_lanes()
    aggs, fired = p.store.replay.replay_columns(lanes, watermark=p.now)
    keys = [lanes.key_vocab[c] for c in lanes.key_codes]
    ref = reference_reduce(keys, tumbling_starts(lanes.ts, WINDOW_S),
                           lanes.values)
    return {"phase": "log_replay", "events": lanes.count,
            "alerts": len(fired),
            **compare("log_replay", _agg_lanes(aggs), ref)}


def check_direct(lanes, *,
                 n_events: int = KERNEL_EVENTS,
                 n_segments: int = KERNEL_SEGMENTS,
                 seed: int = SEED) -> dict:
    """(d): one ``ops.window_reduce`` call at the benchmark's kernel size,
    and one sliding-window ``pack_columns`` batch over ``lanes``."""
    from repro.alerts import WindowSpec
    from repro.alerts.batch import pack_columns
    from repro.kernels import ops

    rng = np.random.default_rng(seed)
    vals = rng.normal(size=n_events).astype(np.float32)
    segs = rng.integers(0, n_segments, size=n_events).astype(np.int32)
    out = np.asarray(ops.window_reduce(vals, segs, n_segments))
    _check(out.shape == (n_segments, 4), f"direct: shape {out.shape}")
    _check(bool(np.isfinite(out[:, :3]).all()), "direct: non-finite lanes")
    ref = reference_reduce(segs.tolist(), np.zeros(n_events), vals)
    got = {}
    for s in range(n_segments):
        cnt, sm, sq, mx = (float(x) for x in out[s])
        if (s, 0.0) in ref:
            got[(s, 0.0)] = (cnt, sm, sq, mx)
        else:
            _check(cnt == 0 and sm == 0 and mx == -math.inf,
                   f"direct: empty segment {s} reads {out[s]}")
    worst = [compare("direct", got, ref)]

    svals = rng.normal(size=lanes.count)
    spec = WindowSpec(kind="sliding", size_s=SLIDING_SIZE_S,
                      slide_s=SLIDING_SLIDE_S)
    packed, seg_ids, slots = pack_columns(lanes.ts, lanes.key_codes,
                                          svals, spec)
    sout = np.asarray(ops.window_reduce(packed, seg_ids, len(slots)))
    got = {(code, start): tuple(float(x) for x in sout[i])
           for i, (code, start, _end) in enumerate(slots)}
    idx, starts = sliding_expand(lanes.ts, SLIDING_SIZE_S, SLIDING_SLIDE_S)
    ref = reference_reduce(lanes.key_codes[idx].tolist(), starts,
                           svals[idx].astype(np.float32))
    worst.append(compare("direct_sliding", got, ref))
    return {"phase": "direct", "events": n_events, "segments": n_segments,
            "sliding_events": int(packed.shape[0]),
            "sliding_slots": len(slots), **_merge_worst(worst)}


def lowered_is_native(n_events: int = KERNEL_EVENTS,
                      n_segments: int = KERNEL_SEGMENTS) -> bool:
    import jax
    import jax.numpy as jnp
    from repro.kernels.window_reduce import window_reduce_fwd

    text = window_reduce_fwd.lower(
        jax.ShapeDtypeStruct((n_events,), jnp.float32),
        jax.ShapeDtypeStruct((n_events,), jnp.int32),
        num_segments=n_segments, interpret=False).as_text()
    return "tpu_custom_call" in text


# ---- entry point -----------------------------------------------------------

def main() -> int:
    os.environ.setdefault("JAX_PLATFORMS", "tpu")
    import jax

    devices = jax.devices()
    dev = devices[0]
    _check(dev.platform == "tpu",
           f"JAX found no TPU (platform {dev.platform!r}); this smoke "
           f"runs only on the chip")
    sys.path.insert(0, str(SRC))
    from repro.compile_cache import enable_compile_cache

    cache_dir = enable_compile_cache()
    comp = CompileMonitor()
    comp.install()
    launches0 = launch_counts()
    walls: Dict[str, float] = {}
    summary: Dict[str, object] = {
        "device_kind": dev.device_kind, "device_count": len(devices),
        "compile_cache_dir": cache_dir, "num_sources": NUM_SOURCES,
        "virtual_s": VIRTUAL_S}
    worst = []
    with tempfile.TemporaryDirectory(prefix="chip_smoke_") as store_dir:
        p = build_pipeline(store_dir)
        try:
            t0 = time.perf_counter()
            res = run_with_late_drains(p)
            walls["late_drain"] = time.perf_counter() - t0
            _emit(res)
            worst.append(res)
            summary.update(
                events_ingested=p.analytics.operator.stats["events"],
                windows_closed=p.analytics.closed_total,
                alerts_fired=p.metrics.alerts_total)
            for name, fn in (("cold_query", check_cold_query),
                             ("log_replay", check_log_replay)):
                t0 = time.perf_counter()
                res = fn(p)
                walls[name] = time.perf_counter() - t0
                _emit(res)
                if "sum_abs_err" in res:
                    worst.append(res)
            t0 = time.perf_counter()
            res = check_direct(p.store.log.scan_lanes())
            walls["direct"] = time.perf_counter() - t0
            _emit(res)
            worst.append(res)
        finally:
            p.close()
    counts = launches_since(launches0)
    per_route = {r: c["launches"] for r, c in counts.items()}
    for route in ROUTES:
        _check(per_route.get(route, 0) > 0,
               f"route {route} launched no kernel")
    interpreted = sum(c["interpreted"] for c in counts.values())
    _check(interpreted == 0,
           f"{interpreted} kernel launches ran in interpret mode, not "
           f"natively")
    _check(lowered_is_native(), "lowered window_reduce_fwd holds no "
           "tpu_custom_call")
    stats = dev.memory_stats() or {}
    summary.update(
        launches_per_route=per_route,
        launch_shapes=sum(c["new_shapes"] for c in counts.values()),
        interpreted_launches=interpreted, native_lowering=True,
        wall_s=walls, worst=_merge_worst(worst),
        peak_bytes_in_use=stats.get("peak_bytes_in_use"),
        compilations=comp.snapshot())
    _emit(summary)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
