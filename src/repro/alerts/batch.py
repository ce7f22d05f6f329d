"""Bridge between host-side windowing and the Pallas segment reduction.

Every batch route — log replay, the late-event drain, cold-range
queries — reaches the kernel through one path: column lanes (event
times, key codes into a vocabulary, values) -> ``pack_columns``, which
flattens them into the flat ``values`` / ``seg_ids`` tensors
``repro.kernels.ops.window_reduce`` consumes (one segment per distinct
(key, window) slot — sliding windows replicate an event into every
covering slot, and session windows make one slot per session the
batch's events form) -> the kernel -> ``reduce_columns``' unpack of its
(S, 4) count/sum/sumsq/max lanes into ``WindowAggregate`` records.
(key, event_time, value) triples enter the same path once their keys
are coded (``code_events``; ``pack_events`` and ``reduce_events`` are
its fronts).  This is the batch/replay path — reprocessing a backlog of
documents at hardware speed — complementing the incremental
``WindowOperator`` used on the live path; both produce identical
aggregates (tested), so rules don't care which path fed them.
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

import numpy as np

from repro.alerts.windows import (SESSION, TUMBLING, WindowAggregate,
                                  WindowSpec)
from repro.obs.launches import record_pack_sessions, record_pack_slot_index

Event = Tuple[str, float, float]          # (key, event_time, value)
Slot = Tuple[str, float, float]           # (key, window_start, window_end)

# pack_columns indexes its (start, code) keys with a presence table and
# its running count (5 bytes a key of the range, in place of a sort of
# the memberships' keys) when the range is at most this many times the
# memberships; past it, sorting the keys costs less memory and time
_DENSE_KEYS_PER_MEMBERSHIP = 4


def code_events(events: Sequence[Event]):
    """(key, event_time, value) triples -> the column arrays
    ``reduce_columns`` takes: (ts f64, key codes i64, values f64, vocab),
    the keys coded in sorted order."""
    vocab, codes = np.unique(np.asarray([e[0] for e in events], object),
                             return_inverse=True)
    return (np.asarray([e[1] for e in events], np.float64),
            codes.astype(np.int64),
            np.asarray([e[2] for e in events], np.float64), vocab.tolist())


def pack_events(events: Sequence[Event], spec: WindowSpec):
    """-> (values f32 (N,), seg_ids i32 (N,), slots list[Slot]).

    ``pack_columns`` over the events' coded keys (``code_events``), with
    each slot's code mapped back to its key, so slots come in (start,
    key) order for every window kind."""
    ts, codes, values, vocab = code_events(events)
    packed, seg_ids, slots = pack_columns(ts, codes, values, spec)
    return packed, seg_ids, [(vocab[c], s, e) for c, s, e in slots]


def pack_columns(ts: np.ndarray, key_codes: np.ndarray,
                 values: np.ndarray, spec: WindowSpec, *, profiler=None):
    """Pack COLUMN arrays (a log's ``scan_lanes`` output) for the
    kernel: no per-event Python at all.

    -> (values f32 (N,), seg_ids i32 (N,), slots list[(key_code,
    start, end)]).  Window starts replicate ``WindowSpec.assign``'s
    exact float arithmetic (tumbling: one floor-multiply; sliding: the
    same repeated subtraction, vectorized per step) so slots are
    bit-identical to the live operator's — the hot/cold dedup in the
    query plane depends on it.

    Slots come in (start, key code) order, each membership's seg id is
    its slot's index in that order.  The slot index is built on one
    int64 key, ``rank(start) * K + code - min(code)`` with ``K`` the
    codes' range, which keeps that order: the starts are ranked
    exactly (``np.unique`` of the float64 column, a sort on both
    paths, then ``searchsorted``), and the keys are indexed by a
    presence table and its running count when the key's range is at
    most ``_DENSE_KEYS_PER_MEMBERSHIP`` times the memberships (path
    ``dense``, which does not sort the keys), else by ``np.unique`` of
    the keys (path ``sort``).  Each call counts its path in
    ``repro.obs.pack_slot_index()``.

    Session windows take their own layout (``_pack_sessions``): one
    slot per session, whose end is its last event plus ``gap_s``.

    ``profiler`` times the three steps as sub-stages of ``pack_events``:
    ``pack_events.assign`` (window assignment and expansion),
    ``pack_events.unique`` (ranking the starts, the integer key, its
    index and inverse, and each slot's start and code) and
    ``pack_events.slots`` (the Python slot list)."""
    stage = _stage_of(profiler)
    if spec.kind == SESSION:
        return _pack_sessions(ts, key_codes, values, spec.gap_s, stage)
    with stage("pack_events.assign"):
        ts = np.asarray(ts, np.float64)
        codes = np.asarray(key_codes, np.int64)
        vals = np.asarray(values, np.float64)
        if ts.size == 0:
            return (np.empty(0, np.float32), np.empty(0, np.int32), [])
        if spec.kind == TUMBLING:
            estarts = np.floor(ts / spec.size_s) * spec.size_s
            ecodes, evals = codes, vals
        else:                             # SLIDING
            slide = float(spec.slide_s)
            cur = np.floor(ts / slide) * slide
            lower = ts - spec.size_s
            parts_s: List[np.ndarray] = []
            parts_c: List[np.ndarray] = []
            parts_v: List[np.ndarray] = []
            while True:
                m = cur > lower
                if not m.any():
                    break
                parts_s.append(cur[m])
                parts_c.append(codes[m])
                parts_v.append(vals[m])
                cur = cur - slide
            estarts = np.concatenate(parts_s)
            ecodes = np.concatenate(parts_c)
            evals = np.concatenate(parts_v)
        packed = evals.astype(np.float32)
    with stage("pack_events.unique"):
        # one slot per distinct (start, code) pair, in that order
        starts = np.unique(estarts)
        lo = int(ecodes.min())
        k = int(ecodes.max()) - lo + 1
        key = np.searchsorted(starts, estarts) * k + (ecodes - lo)
        span = starts.size * k
        if span <= _DENSE_KEYS_PER_MEMBERSHIP * key.size:
            path = "dense"
            present = np.zeros(span, bool)
            present[key] = True
            seg_ids = np.cumsum(present, dtype=np.int32)[key] - 1
            ukey = np.flatnonzero(present)
        else:
            path = "sort"
            ukey, inv = np.unique(key, return_inverse=True)
            seg_ids = inv.astype(np.int32)
        ustarts = starts[ukey // k]
        ucodes = ukey % k + lo
    record_pack_slot_index(path)
    with stage("pack_events.slots"):
        slots = [(c, s, s + spec.size_s)
                 for s, c in zip(ustarts.tolist(), ucodes.tolist())]
    return (packed, seg_ids, slots)


def _session_opens(codes: np.ndarray, ts: np.ndarray, gap_s: float):
    """Over events sorted by (code, ts) -> (by_key, by_gap): whether each
    event opens a session because its key differs from the previous
    event's, or because it comes more than ``gap_s`` after it.  The gap
    test is the live operator's closed-interval overlap, ``previous +
    gap_s < t``, so events exactly ``gap_s`` apart share a session."""
    by_key = np.ones(ts.size, bool)
    by_key[1:] = codes[1:] != codes[:-1]
    by_gap = np.zeros(ts.size, bool)
    by_gap[1:] = ~by_key[1:] & (ts[:-1] + gap_s < ts[1:])
    return by_key, by_gap


def _pack_sessions(ts, key_codes, values, gap_s: float, stage):
    """``pack_columns`` for session windows.

    The events are sorted by (code, event time) and cut where the code
    changes or the next event comes more than ``gap_s`` later
    (``_session_opens``).  A session's start is its first event and its
    end its last event plus ``gap_s``, as the live operator merges them;
    within a key, sessions are disjoint, so (start, code) is unique and
    the slots come in that order, like the other kinds'.  Each call
    counts the ``session`` path in ``repro.obs.pack_slot_index()`` and
    its sessions, by what opened them, in ``repro.obs.pack_sessions()``.

    Timed as ``pack_events.sessions`` (the sort, the cut, the seg ids
    and each session's start and end) and ``pack_events.slots`` (the
    Python slot list)."""
    with stage("pack_events.sessions"):
        ts = np.asarray(ts, np.float64)
        codes = np.asarray(key_codes, np.int64)
        if ts.size == 0:
            return (np.empty(0, np.float32), np.empty(0, np.int32), [])
        packed = np.asarray(values, np.float64).astype(np.float32)
        order = np.lexsort((ts, codes))
        c, t = codes[order], ts[order]
        by_key, by_gap = _session_opens(c, t, gap_s)
        opens = by_key | by_gap
        first = np.flatnonzero(opens)
        last = np.append(first[1:], t.size) - 1
        starts, ends, scodes = t[first], t[last] + gap_s, c[first]
        # sessions in (start, code) order; each event's seg id is its
        # session's index in that order
        slot_order = np.lexsort((scodes, starts))
        rank = np.empty(first.size, np.int32)
        rank[slot_order] = np.arange(first.size, dtype=np.int32)
        seg_ids = np.empty(t.size, np.int32)
        seg_ids[order] = rank[np.cumsum(opens) - 1]
    record_pack_slot_index("session")
    record_pack_sessions(key=int(by_key.sum()), gap=int(by_gap.sum()))
    with stage("pack_events.slots"):
        slots = list(zip(scodes[slot_order].tolist(),
                         starts[slot_order].tolist(),
                         ends[slot_order].tolist()))
    return (packed, seg_ids, slots)


def reduce_columns(ts: np.ndarray, key_codes: np.ndarray,
                   values: np.ndarray, key_vocab: Sequence[str],
                   spec: WindowSpec, *, interpret=None, profiler=None,
                   with_min: bool = False,
                   route: str = "direct") -> List[WindowAggregate]:
    """One kernel launch -> WindowAggregates for every touched slot:
    pack_columns -> window_reduce -> unpack.  Per-record Python appears
    only in the final per-SLOT unpack (S slots, not N events); a slot's
    key is ``key_vocab[code]``.

    ``profiler`` (a ``repro.obs.StageProfiler``) itemizes the chain into
    pack_events / kernel / unpack stages, the kernel stage into
    ``kernel.dispatch`` / ``kernel.wait`` / ``kernel.fetch`` per launch.
    ``route`` names the caller for the kernel's launch counters.

    ``with_min=True`` adds a second launch over the negated values —
    ``min(v) = -max(-v)`` — so per-slot minima come out of the same
    4-lane kernel without changing its pinned (S, 4) output shape.  The
    query plane (repro.query) needs min; the rule engine's live path
    already tracks it incrementally."""
    stage = _stage_of(profiler)
    with stage("pack_events"):
        packed, seg_ids, slots = pack_columns(
            ts, key_codes, values, spec, profiler=profiler)
    if not slots:
        return []
    with stage("kernel"):
        # the max lane is dispatched, waited on and fetched before the
        # min lane is dispatched
        lanes = _kernel_lane(packed, seg_ids, len(slots), stage=stage,
                             interpret=interpret, route=route)
        mins = None
        if with_min:
            mins = -_kernel_lane(packed, seg_ids, len(slots), stage=stage,
                                 interpret=interpret, route=route,
                                 negate=True)[:, 3]
    with stage("unpack"):
        out: List[WindowAggregate] = []
        for sid, (code, start, end) in enumerate(slots):
            cnt, sm, sq, mx = lanes[sid]
            agg = WindowAggregate(
                key=key_vocab[code], window_start=start, window_end=end,
                count=int(round(cnt)), sum=float(sm), sumsq=float(sq),
                max=float(mx))
            if mins is not None:
                agg.min = float(mins[sid])
            out.append(agg)
        out.sort(key=lambda a: (a.window_end, a.key))
    return out


def reduce_events(events: Sequence[Event], spec: WindowSpec, *,
                  interpret=None, profiler=None, with_min: bool = False,
                  route: str = "direct") -> List[WindowAggregate]:
    """``reduce_columns`` over (key, event_time, value) triples, their
    keys coded by ``code_events``."""
    return reduce_columns(*code_events(events), spec, interpret=interpret,
                          profiler=profiler, with_min=with_min, route=route)


def _kernel_lane(values, seg_ids, num_slots: int, *, stage, interpret,
                 route: str, negate: bool = False) -> np.ndarray:
    """One ``window_reduce`` launch, timed as ``kernel.dispatch`` (until
    the call returns), ``kernel.wait`` (until the device is done) and
    ``kernel.fetch`` (the copy to the host)."""
    from repro.kernels import ops   # lazy: keep host path jax-free

    with stage("kernel.dispatch"):
        out = ops.window_reduce(-values if negate else values, seg_ids,
                                num_slots, interpret=interpret, route=route)
    with stage("kernel.wait"):
        out.block_until_ready()
    with stage("kernel.fetch"):
        return np.asarray(out)


class _NullStage:
    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        pass


_NULL_STAGE = _NullStage()


def _null_stage(name: str) -> _NullStage:
    return _NULL_STAGE


def _stage_of(profiler):
    return profiler.stage if profiler is not None else _null_stage
