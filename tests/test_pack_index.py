"""``pack_columns``' slot index against the row-wise packer it replaced.

``frozen_pack_columns`` is the column packer as it was before the slot
index moved to one integer key: window assignment, then
``np.unique(axis=0)`` over (start, code) float64 pairs.  The packer
under test must give the same ``(packed, seg_ids, slots)`` byte for
byte, whichever path (``dense`` presence table or ``sort``) it takes,
and count that path in ``repro.obs.pack_slot_index()``.
"""
import numpy as np
import pytest
from _hyp import given, settings, st

from repro.alerts.batch import _DENSE_KEYS_PER_MEMBERSHIP, pack_columns
from repro.alerts.windows import WindowSpec
from repro.obs import pack_slot_index


def frozen_pack_columns(ts, key_codes, values, spec):
    ts = np.asarray(ts, np.float64)
    codes = np.asarray(key_codes, np.int64)
    vals = np.asarray(values, np.float64)
    if spec.kind == "tumbling":
        estarts = np.floor(ts / spec.size_s) * spec.size_s
        ecodes, evals = codes, vals
    else:
        slide = float(spec.slide_s)
        cur = np.floor(ts / slide) * slide
        lower = ts - spec.size_s
        parts_s, parts_c, parts_v = [], [], []
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
    combo = np.column_stack([estarts, ecodes.astype(np.float64)])
    uniq, inv = np.unique(combo, axis=0, return_inverse=True)
    slots = [(int(c), float(s), float(s) + spec.size_s) for s, c in uniq]
    return evals.astype(np.float32), inv.astype(np.int32).ravel(), slots


def _assert_identical(got, want):
    packed, seg_ids, slots = got
    w_packed, w_seg_ids, w_slots = want
    assert packed.dtype == np.float32 and seg_ids.dtype == np.int32
    assert packed.tobytes() == w_packed.tobytes()
    assert seg_ids.tobytes() == w_seg_ids.tobytes()
    assert len(slots) == len(w_slots)
    assert [c for c, _, _ in slots] == [c for c, _, _ in w_slots]
    assert all(type(c) is int and type(s) is float and type(e) is float
               for c, s, e in slots)
    for i in (1, 2):                       # starts, then ends, as bytes
        assert (np.float64([s[i] for s in slots]).tobytes()
                == np.float64([s[i] for s in w_slots]).tobytes())


def _paths_taken(fn):
    before = pack_slot_index()
    out = fn()
    after = pack_slot_index()
    return out, {p: after[p] - before[p] for p in after
                 if after[p] != before[p]}


TUMBLING_60 = WindowSpec(kind="tumbling", size_s=60.0)
TUMBLING_7_5 = WindowSpec(kind="tumbling", size_s=7.5)
SLIDING_10_5 = WindowSpec(kind="sliding", size_s=10.0, slide_s=5.0)
SLIDING_1_0_1 = WindowSpec(kind="sliding", size_s=1.0, slide_s=0.1)
SLIDING_0_9_0_3 = WindowSpec(kind="sliding", size_s=0.9, slide_s=0.3)
SLIDING_7_5_2_5 = WindowSpec(kind="sliding", size_s=7.5, slide_s=2.5)

# (spec, events, distinct keys, code stride, first ts, ts span, sorted,
#  path): codes are multiples of the stride, so a stride above 1 leaves
# codes of the vocabulary the data never uses
CASES = {
    "tumbling": (TUMBLING_60, 2000, 4, 1, 0.0, 3600.0, False, "dense"),
    "tumbling_sorted": (TUMBLING_60, 2000, 4, 1, 0.0, 3600.0, True,
                        "dense"),
    "tumbling_7_5_negative": (TUMBLING_7_5, 3000, 16, 1, -500.0, 400.0,
                              False, "dense"),
    "sliding_int": (SLIDING_10_5, 5000, 300, 1, 0.0, 300.0, False,
                    "dense"),
    "sliding_int_sorted": (SLIDING_10_5, 5000, 300, 1, 0.0, 300.0, True,
                           "dense"),
    "sliding_0_1": (SLIDING_1_0_1, 3000, 3, 1, 0.0, 50.0, False, "dense"),
    "sliding_0_3": (SLIDING_0_9_0_3, 3000, 5, 1, 10.0, 90.0, False,
                    "dense"),
    "sliding_2_5_over_7_5": (SLIDING_7_5_2_5, 4000, 40, 1, 0.0, 200.0,
                             False, "dense"),
    "sliding_negative": (SLIDING_7_5_2_5, 4000, 40, 1, -1000.0, 500.0,
                         False, "dense"),
    "sliding_0_1_negative": (SLIDING_1_0_1, 2000, 2, 1, -30.0, 25.0, True,
                             "dense"),
    "large_ts_tumbling": (TUMBLING_60, 3000, 8, 1, 1e9, 7200.0, False,
                          "dense"),
    "large_ts_sliding_0_1": (SLIDING_1_0_1, 3000, 3, 1, 1e9, 40.0, False,
                             "dense"),
    "large_ts_sliding_0_3_sorted": (SLIDING_0_9_0_3, 3000, 3, 1, 1e9,
                                    60.0, True, "dense"),
    "one_event_tumbling": (TUMBLING_60, 1, 1, 1, 123.4, 0.0, False,
                           "dense"),
    "one_event_sliding_0_1": (SLIDING_1_0_1, 1, 1, 1, 1e9 + 0.05, 0.0,
                              False, "dense"),
    "one_key": (SLIDING_10_5, 2000, 1, 1, 0.0, 600.0, False, "dense"),
    "unused_codes": (SLIDING_10_5, 2000, 6, 7, 0.0, 120.0, False, "dense"),
    "sort_sparse_codes": (SLIDING_0_9_0_3, 300, 50, 997, 0.0, 100.0,
                          False, "sort"),
    "sort_many_windows": (TUMBLING_7_5, 500, 20, 1, -1e5, 2e5, False,
                          "sort"),
    "sort_large_ts_0_1": (SLIDING_1_0_1, 400, 30, 13, 1e9, 500.0, True,
                          "sort"),
    "sort_two_events_sparse_vocab": (TUMBLING_60, 2, 2, 5000, 1e9, 1e4,
                                     False, "sort"),
    "sort_one_window_wide_vocab": (TUMBLING_60, 10, 10, 4999, 30.0, 1.0,
                                   False, "sort"),
    "sort_many_windows_sliding_0_1": (SLIDING_1_0_1, 2000, 40, 3, -4e4,
                                      8e4, False, "sort"),
}


def _columns(n, n_keys, stride, t0, t_span, sort, seed):
    rng = np.random.default_rng(seed)
    ts = t0 + rng.random(n) * t_span
    if sort:
        ts = np.sort(ts)
    codes = rng.integers(0, n_keys, n) * stride
    codes[:n_keys] = np.arange(min(n, n_keys)) * stride
    values = rng.normal(size=n) * 100.0
    return ts, codes, values


@pytest.mark.parametrize("case", sorted(CASES))
def test_pack_columns_matches_row_wise_unique(case):
    spec, n, n_keys, stride, t0, t_span, sort, path = CASES[case]
    ts, codes, values = _columns(n, n_keys, stride, t0, t_span, sort,
                                 seed=sorted(CASES).index(case))
    got, paths = _paths_taken(lambda: pack_columns(ts, codes, values, spec))
    assert paths == {path: 1}
    _assert_identical(got, frozen_pack_columns(ts, codes, values, spec))


def test_pack_columns_codes_far_from_zero_stay_dense():
    """The key is offset by the smallest code, so a vocabulary slice far
    from code 0 still takes the presence table."""
    ts, codes, values = _columns(1000, 10, 1, 0.0, 600.0, False, seed=1)
    codes = codes + 10**9
    got, paths = _paths_taken(
        lambda: pack_columns(ts, codes, values, SLIDING_10_5))
    assert paths == {"dense": 1}
    _assert_identical(got, frozen_pack_columns(ts, codes, values,
                                               SLIDING_10_5))


def test_pack_columns_path_follows_the_keys_range():
    """The presence table serves up to the fixed multiple of the
    memberships, and the sort serves one key past it."""
    m = 10
    for width, path in ((_DENSE_KEYS_PER_MEMBERSHIP * m, "dense"),
                        (_DENSE_KEYS_PER_MEMBERSHIP * m + 1, "sort")):
        codes = np.arange(m)
        codes[-1] = width - 1              # one start: the range is K
        ts = np.full(m, 30.0)
        got, paths = _paths_taken(
            lambda: pack_columns(ts, codes, np.ones(m), TUMBLING_60))
        assert paths == {path: 1}
        _assert_identical(got, frozen_pack_columns(ts, codes, np.ones(m),
                                                   TUMBLING_60))


def test_pack_columns_negative_zero_start_is_the_zero_window():
    """A timestamp of -0.0 starts the window at 0.0: one slot per key,
    the same seg ids; the sign of that zero start is not kept per key."""
    ts = np.array([-0.0, 0.0, 0.0, -0.0])
    codes = np.array([0, 1, 0, 1])
    packed, seg_ids, slots = pack_columns(ts, codes, np.ones(4),
                                          TUMBLING_60)
    w_packed, w_seg_ids, w_slots = frozen_pack_columns(ts, codes,
                                                       np.ones(4),
                                                       TUMBLING_60)
    assert seg_ids.tolist() == w_seg_ids.tolist() == [0, 1, 0, 1]
    assert slots == w_slots == [(0, 0.0, 60.0), (1, 0.0, 60.0)]


def test_pack_columns_empty_counts_no_path():
    (packed, seg_ids, slots), paths = _paths_taken(
        lambda: pack_columns(np.empty(0), np.empty(0, np.int64),
                             np.empty(0), TUMBLING_60))
    assert packed.size == seg_ids.size == 0 and slots == []
    assert paths == {}


@settings(max_examples=60, deadline=None)
@given(st.lists(st.tuples(st.floats(-1e4, 1e4, allow_nan=False,
                                    allow_subnormal=False),
                          st.integers(0, 40)), min_size=1, max_size=60),
       st.sampled_from([TUMBLING_60, TUMBLING_7_5, SLIDING_10_5,
                        SLIDING_1_0_1, SLIDING_0_9_0_3, SLIDING_7_5_2_5]))
def test_pack_columns_matches_row_wise_unique_property(events, spec):
    ts = np.array([t for t, _ in events]) + 0.0   # no -0.0 timestamps
    codes = np.array([c for _, c in events])
    values = np.arange(len(events), dtype=np.float64)
    _assert_identical(pack_columns(ts, codes, values, spec),
                      frozen_pack_columns(ts, codes, values, spec))
