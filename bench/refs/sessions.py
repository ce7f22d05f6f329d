"""Plain reference for session windows.

A session is one key's events cut at idle gaps: each key's events in
event-time order, a new session wherever an event comes more than
``gap_s`` after the previous one (events exactly ``gap_s`` apart share a
session).  A session starts at its first event and ends at its last
event plus ``gap_s``.

``sessionize`` walks the events one by one in (key, time) order and
gives each its session's start; the lanes then come from
``bench.refs.reduce``'s float64 group-by over (key, session start), and
its bfloat16 control, whose session ends ``control_ends`` computes in
bfloat16 too.  Nothing of the program is imported.
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple

import ml_dtypes
import numpy as np

from bench.refs import reduce
from bench.refs.reduce import Slot

READINGS = ("session_end_mismatch",)


def sessionize(keys: np.ndarray, ts: np.ndarray, gap_s: float
               ) -> Tuple[np.ndarray, Dict[Slot, float]]:
    """-> (each event's session start, {(key, start): end}), slot keys
    as strings, as the pipeline writes them."""
    ts = np.asarray(ts, np.float64)
    order = np.lexsort((ts, keys))
    starts = np.empty(ts.size, np.float64)
    ends: Dict[Slot, float] = {}
    key = start = last = None
    for i, k, t in zip(order.tolist(), np.asarray(keys)[order].tolist(),
                       ts[order].tolist()):
        if k != key or t > last + gap_s:
            if key is not None:
                ends[(str(key), start)] = last + gap_s
            key, start = k, t
        last = t
        starts[i] = start
    if key is not None:
        ends[(str(key), start)] = last + gap_s
    return starts, ends


def control_ends(ends: Dict[Slot, float], gap_s: float) -> Dict[Slot, float]:
    """The reference's session ends one precision below float32: each
    session's last event plus the gap, added in bfloat16."""
    bf16 = ml_dtypes.bfloat16
    return {slot: float(bf16(end - gap_s) + bf16(gap_s))
            for slot, end in ends.items()}


def session_ends(aggs) -> Dict[Slot, float]:
    """WindowAggregate-like records -> {(key, start): end}."""
    return {(a.key, a.window_start): a.window_end for a in aggs}


def compare_sessions(got_lanes: Dict[Slot, tuple],
                     got_ends: Dict[Slot, float], ref: Dict[Slot, tuple],
                     ref_ends: Dict[Slot, float]) -> dict:
    """``bench.refs.reduce.compare`` plus ``session_end_mismatch``: the
    reference's sessions whose end the output gives otherwise."""
    out = reduce.compare(got_lanes, ref)
    out["session_end_mismatch"] = sum(
        1 for slot, end in ref_ends.items()
        if slot in got_ends and got_ends[slot] != end)
    return out


def merge_session_readings(parts: Sequence[dict]) -> dict:
    """Exact counts add up over the compared replays; ratios keep the
    worst."""
    exact = reduce.READINGS[:3] + READINGS
    out = reduce.empty_readings() | {k: 0 for k in READINGS}
    for r in parts:
        for k in exact:
            out[k] += r[k]
        for k in reduce.READINGS[3:]:
            out[k] = max(out[k], r[k])
    return out
