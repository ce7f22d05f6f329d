"""Plain references for the windowed reductions the benchmark checks.

``reference_reduce``, ``sliding_expand``, the float32 accumulation bound
and the exact/bounded comparison are copied from the repository's
``chip_smoke.py`` (its references for the kernel routes), extended with
the min lane.  They import nothing of the program.

A slot is ``(key, window start)`` and its lanes are
``(count, sum, sumsq, max, min)``.  The reference works in float64 from
values rounded to float32, as the kernel receives them.  Counts, max and
min must match exactly; sum and sum of squares must fall within the
float32 accumulation bound for the slot's n terms in any summation order
(Higham, Accuracy and Stability of Numerical Algorithms, 2nd ed., 4.2),
with u = 2**-24 and g(k) = k*u / (1 - k*u):

    |sum - ref|   <= g(n-1) * sum|v|   + n * 2**-126 + 2n * 2**-53 * sum|v|
    |sumsq - ref| <= g(n)   * sum(v^2) + n * 2**-126 + 2n * 2**-53 * sum(v^2)

The second term covers a flush of subnormal results to zero and the third
the float64 reference's own rounding.

``control_reduce`` is the reference computed one precision lower: values
and every accumulation in bfloat16.  Put in the program's place, it has
to fail the comparison.
"""
from __future__ import annotations

import math
from typing import Dict, Hashable, List, Sequence, Tuple

import ml_dtypes
import numpy as np

Slot = Tuple[Hashable, float]          # (key, window start)
Lanes = Tuple[float, float, float, float, float]  # count, sum, sumsq, max, min

_U = 2.0 ** -24             # float32 unit roundoff
_U64 = 2.0 ** -53           # float64 unit roundoff
_TINY = 2.0 ** -126         # smallest normal float32


def _group(keys: Sequence[Hashable], starts: np.ndarray):
    """-> (vocab, order, first, codes, starts) grouping rows by slot;
    slot keys are the keys as strings, as the pipeline writes them."""
    if isinstance(keys, np.ndarray):
        uniq, codes = np.unique(keys, return_inverse=True)
        vocab = [str(k) for k in uniq.tolist()]
        codes = codes.ravel().astype(np.int64)
    else:
        codes_of: Dict[Hashable, int] = {}
        codes = np.fromiter(
            (codes_of.setdefault(k, len(codes_of)) for k in keys),
            dtype=np.int64, count=len(keys))
        vocab = [str(k) for k in codes_of]
    starts = np.asarray(starts, np.float64)
    order = np.lexsort((starts, codes))
    c, s = codes[order], starts[order]
    cut = np.flatnonzero((np.diff(c) != 0) | (np.diff(s) != 0)) + 1
    first = np.concatenate([[0], cut]).astype(np.int64)
    return vocab, order, first, c, s


def reference_reduce(keys: Sequence[Hashable], starts: np.ndarray,
                     values: np.ndarray) -> Dict[Slot, tuple]:
    """float64 group-by over (key, window start) ->
    (count, sum, sumsq, max, min, sum|v|), from values rounded to
    float32 as the kernel receives them."""
    if len(keys) == 0:
        return {}
    vocab, order, first, c, s = _group(keys, starts)
    v = np.asarray(values, np.float32).astype(np.float64)[order]
    counts = np.diff(np.concatenate([first, [c.size]]))
    sums = np.add.reduceat(v, first)
    sumsq = np.add.reduceat(v * v, first)
    maxes = np.maximum.reduceat(v, first)
    mins = np.minimum.reduceat(v, first)
    sumabs = np.add.reduceat(np.abs(v), first)
    return {(vocab[c[i]], float(s[i])):
            (int(counts[j]), float(sums[j]), float(sumsq[j]),
             float(maxes[j]), float(mins[j]), float(sumabs[j]))
            for j, i in enumerate(first)}


def control_reduce(keys: Sequence[Hashable], starts: np.ndarray,
                   values: np.ndarray) -> Dict[Slot, Lanes]:
    """The reference one precision below float32: values, counts and
    every running sum in bfloat16 (sequential within a slot)."""
    if len(keys) == 0:
        return {}
    bf16 = ml_dtypes.bfloat16
    vocab, order, first, c, s = _group(keys, starts)
    v = np.asarray(values, np.float64).astype(bf16)[order]
    counts = np.add.reduceat(np.ones(v.size, bf16), first)
    sums = np.add.reduceat(v, first)
    sumsq = np.add.reduceat(v * v, first)
    maxes = np.maximum.reduceat(v, first)
    mins = np.minimum.reduceat(v, first)
    return {(vocab[c[i]], float(s[i])):
            (float(counts[j]), float(sums[j]), float(sumsq[j]),
             float(maxes[j]), float(mins[j]))
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


def window_rows(ts: np.ndarray, spec: dict):
    """(event index, window start) for every (event, window) membership
    that the window spec gives: one per event when tumbling."""
    ts = np.asarray(ts, np.float64)
    if spec["kind"] == "tumbling":
        return np.arange(ts.size), tumbling_starts(ts, spec["size_s"])
    return sliding_expand(ts, spec["size_s"], spec["slide_s"])


def _gamma(k: int) -> float:
    return k * _U / (1.0 - k * _U)


#: the comparison's numbers: exact ones first, then the bounded sums
READINGS = ("slots_differ", "count_mismatch", "extreme_mismatch",
            "sum_err_over_bound", "sumsq_err_over_bound")


def empty_readings() -> dict:
    return {k: 0 for k in READINGS[:3]} | {k: 0.0 for k in READINGS[3:]}


def compare(got: Dict[Slot, Lanes], ref: Dict[Slot, tuple]) -> dict:
    """Exact slots/count/max/min, bounded sum/sumsq -> the readings: how
    many slots are missing or extra, how many counts and extremes differ,
    and the worst sum and sumsq error as a share of its bound."""
    out = empty_readings()
    out["slots_differ"] = len(set(got).symmetric_difference(ref))
    for slot, (rc, rs, rsq, rmx, rmn, rabs) in ref.items():
        lanes = got.get(slot)
        if lanes is None:
            continue
        cnt, sm, sq, mx, mn = lanes
        if cnt != rc:
            out["count_mismatch"] += 1
        if mx != rmx or mn != rmn:
            out["extreme_mismatch"] += 1
        b_sum = _gamma(rc - 1) * rabs + rc * _TINY + 2 * rc * _U64 * rabs
        b_sq = _gamma(rc) * rsq + rc * _TINY + 2 * rc * _U64 * rsq
        e_sum, e_sq = abs(sm - rs), abs(sq - rsq)
        out["sum_err_over_bound"] = max(out["sum_err_over_bound"],
                                        _ratio(e_sum, b_sum))
        out["sumsq_err_over_bound"] = max(out["sumsq_err_over_bound"],
                                          _ratio(e_sq, b_sq))
    return out


def _ratio(err: float, bound: float) -> float:
    if not math.isfinite(err):
        return math.inf
    if bound > 0:
        return err / bound
    return 0.0 if err == 0 else math.inf


def merge_readings(parts: Sequence[dict]) -> dict:
    """Exact counts add up over the compared batches; ratios keep the
    worst."""
    out = empty_readings()
    for r in parts:
        for k in READINGS[:3]:
            out[k] += r[k]
        for k in READINGS[3:]:
            out[k] = max(out[k], r[k])
    return out


def agg_lanes(aggs) -> Dict[Slot, Lanes]:
    """WindowAggregate-like records -> {(key, start): lanes}; a second
    record for one slot counts as a mismatch of that slot."""
    out: Dict[Slot, Lanes] = {}
    for a in aggs:
        slot = (a.key, a.window_start)
        lanes = (a.count, a.sum, a.sumsq, a.max, a.min)
        out[slot] = lanes if slot not in out else (math.nan,) * 5
    return out
