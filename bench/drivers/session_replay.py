"""Sealed-log replay of a session-windowed deployment: ``replay.py``'s
closed loop of whole ``replay_log(0)`` calls, checked against the
session reference.

Set-up builds the deployment's pipeline with its store plane, writes the
generator's documents through the store's own writer, seals the log and
replays it once, so that every program the window runs is compiled.  The
window calls ``replay_log(0)`` until ``--seconds`` have passed; the rate
is the log events of the completed calls over the time from the
window's start to the end of the last call.  The aggregates of a sample
of the calls, drawn from the seed (``check_share`` of them, and always
the first), are compared with the generator's events cut into sessions
by ``bench/refs/sessions.py`` and reduced by a float64 group-by: each
session's key, start, count and extremes exactly, its sums within the
float32 bound, and its end exactly (``session_end_mismatch``).
"""
from __future__ import annotations

import time
from types import SimpleNamespace

import numpy as np

from bench.drivers import build_pipeline
from bench.drivers import replay as replay_driver
from bench.gen import log_data
from bench.harness import Check, Outcome, annotate, holds
from bench.refs.reduce import agg_lanes, control_reduce, reference_reduce
from bench.refs.sessions import (compare_sessions, control_ends,
                                 merge_session_readings, session_ends,
                                 sessionize)
from bench.work import window_reduce_work

# the replay stages, and the session pack's and the kernel's sub-stages
STAGES = replay_driver.STAGES + ("pack_events.sessions", "pack_events.slots",
                                 "kernel.wait")


def run(ctx) -> Outcome:
    data = log_data(ctx.config, ctx.seed)
    gap_s = ctx.config["pipeline"]["window_gap_s"]
    p = build_pipeline(ctx.config, store_dir=ctx.tmp)
    for batch in data.chunks():
        p.store.append_documents(batch)
    p.store.log.roll()
    replay = p.store.replay
    exported = []
    p.analytics.add_export(lambda closed, wm: exported.extend(closed))
    watermark = float(data.ts.max()) + gap_s + 1.0
    with annotate("warmup_replay"):
        first = replay.replay_log(0, watermark=watermark)
    if first["events"] != data.count:
        raise RuntimeError(f"the log replays {first['events']} events, the "
                           f"generator wrote {data.count}")
    exported.clear()

    packs0 = _pack_counts()
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
    packs1 = _pack_counts()
    window_s = ends[-1] - t0
    stages = {k: (prof1.get(k, {}).get("total_ms", 0.0)
                  - prof0.get(k, {}).get("total_ms", 0.0)) / 1e3
              for k in STAGES}
    starts, ref_ends = sessionize(data.keys, data.ts, gap_s)
    work = window_reduce_work(data.count, len(ref_ends))
    record = SimpleNamespace(
        route="replay", window_s=window_s, stages=stages,
        compiles=ctx.monitor.executables() - exec0, work=[work] * len(ends))
    took = sorted(e - s for s, e in zip([t0] + ends[:-1], ends))
    info = {"replays": len(ends), "replays_checked": len(outputs),
            "compiles_in_window": record.compiles,
            "events_per_replay": data.count,
            "memberships": data.count, "slots": len(ref_ends),
            "replay_s": {"min": took[0], "median": took[len(took) // 2],
                         "max": took[-1]},
            "stage_s_per_replay": {k: v / len(ends)
                                   for k, v in stages.items()}}
    if packs0 is not None:
        # the program's own counters over the window, per replay: one
        # session pack, and its sessions by what opened them
        info["packs_per_replay"] = {
            k: (packs1[k] - packs0[k]) / len(ends) for k in packs1}

    def check() -> Check:
        ref = reference_reduce(data.keys, starts, data.values)
        parts = [compare_sessions(agg_lanes(out), session_ends(out), ref,
                                  ref_ends) for out in outputs]
        failed = sum(not holds(r, ctx.config["limits"]) for r in parts)
        control = None
        if ctx.control:
            ctl = control_reduce(data.keys, starts, data.values)
            control = compare_sessions(ctl, control_ends(ref_ends, gap_s),
                                       ref, ref_ends)
        return Check(readings=merge_session_readings(parts), failed=failed,
                     control=control)

    return Outcome(end_to_end={"replay_events_per_s": events / window_s},
                   attempted=len(ends), record=record, check=check,
                   info=info)


def _pack_counts():
    """Session packs and their sessions by cut, from the program's
    counters; None from a program without them."""
    try:
        from repro.obs import pack_sessions, pack_slot_index
    except ImportError:
        return None
    return {"session_packs": pack_slot_index().get("session", 0),
            **{f"sessions_{k}": v for k, v in pack_sessions().items()}}
