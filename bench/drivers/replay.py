"""Sealed-log replay: ``ReplayEngine.replay_log`` over the whole log,
called again and again for the window.

Set-up builds the deployment's pipeline with its store plane, writes the
generator's documents through the store's own writer
(``StorePlane.append_documents``), seals the log and replays it once, so
that every program the window runs is compiled.  The window calls
``replay_log(0)`` until ``--seconds`` have passed; the rate is the log
events of the completed calls over the time from the window's start to
the end of the last call.  The aggregates of a sample of the calls,
drawn from the seed (``check_share`` of them, and always the first), as
the replay engine exports them, are compared with a float64 group-by of
the generator's events; the others are dropped as they come, so that
held outputs do not grow the process's heap through the window.
"""
from __future__ import annotations

import time
from types import SimpleNamespace

import numpy as np

from bench.drivers import build_pipeline, window_spec
from bench.gen import log_data
from bench.harness import Check, Outcome, annotate, holds
from bench.refs.reduce import (agg_lanes, compare, control_reduce,
                               merge_readings, reference_reduce, window_rows)
from bench.work import window_reduce_work

STAGES = ("decode", "pack_events", "kernel", "unpack", "state_merge")


def run(ctx) -> Outcome:
    data = log_data(ctx.config, ctx.seed)
    p = build_pipeline(ctx.config, store_dir=ctx.tmp)
    for batch in data.chunks():
        p.store.append_documents(batch)
    p.store.log.roll()
    replay = p.store.replay
    exported = []
    p.analytics.add_export(lambda closed, wm: exported.extend(closed))
    watermark = float(data.ts.max()) + 1.0
    with annotate("warmup_replay"):
        first = replay.replay_log(0, watermark=watermark)
    if first["events"] != data.count:
        raise RuntimeError(f"the log replays {first['events']} events, the "
                           f"generator wrote {data.count}")
    exported.clear()

    pick = np.random.default_rng([ctx.seed, 0xC4EC])
    outputs, ends, events = [], [], 0
    prof0 = replay.profiler.snapshot()
    exec0 = ctx.monitor.executables()
    with ctx.window():
        t0 = ctx.window_bounds[0]
        while True:
            with annotate("replay_log"):
                res = replay.replay_log(0, watermark=watermark)
            ends.append(time.perf_counter())
            events += res["events"]
            if not outputs or pick.random() < ctx.traffic["check_share"]:
                outputs.append(list(exported))
            exported.clear()
            if ends[-1] - t0 >= ctx.seconds:
                break
    prof1 = replay.profiler.snapshot()
    window_s = ends[-1] - t0
    stages = {k: (prof1.get(k, {}).get("total_ms", 0.0)
                  - prof0.get(k, {}).get("total_ms", 0.0)) / 1e3
              for k in STAGES}
    idx, starts = window_rows(data.ts, window_spec(ctx.config))
    slots = int(np.unique(np.column_stack(
        [np.unique(data.keys, return_inverse=True)[1].ravel()[idx],
         starts]), axis=0).shape[0])
    work = window_reduce_work(int(idx.size), slots)
    record = SimpleNamespace(
        route="replay", window_s=window_s, stages=stages,
        compiles=ctx.monitor.executables() - exec0, work=[work] * len(ends))
    took = sorted(e - s for s, e in zip([t0] + ends[:-1], ends))
    info = {"replays": len(ends), "replays_checked": len(outputs),
            "compiles_in_window": record.compiles,
            "events_per_replay": data.count,
            "memberships": int(idx.size), "slots": slots,
            "replay_s": {"min": took[0], "median": took[len(took) // 2],
                         "max": took[-1]}}

    def check() -> Check:
        keys = data.keys[idx]
        ref = reference_reduce(keys, starts, data.values[idx])
        parts = [compare(agg_lanes(out), ref) for out in outputs]
        failed = sum(not holds(r, ctx.config["limits"]) for r in parts)
        control = None
        if ctx.control:
            ctl = control_reduce(keys, starts, data.values[idx])
            control = compare(ctl, ref)
        return Check(readings=merge_readings(parts), failed=failed,
                     control=control)

    return Outcome(end_to_end={"replay_events_per_s": events / window_s},
                   attempted=len(ends), record=record, check=check,
                   info=info)
