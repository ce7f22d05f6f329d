"""``reduce_columns``' unpack against the per-slot loop it replaced.

The unpack orders the pack's slots by (window end, key) with key ranks
and one ``np.lexsort``, then builds each ``WindowAggregate`` once from
``.tolist()`` columns.  On the same packed lanes it must give what the
loop gave: a record built per slot from the lanes' float32 scalars,
``min`` set after, a Python sort on ``(window_end, key)``, the close
stamp set in a later pass.  Every field and the order are compared, the
floats as ``repr``, and each call's path in ``repro.obs.unpack_order()``
(``slot`` where the slots already came in record order, ``sorted``
where they were reordered).  Kernels run in interpret mode.
"""
import numpy as np
import pytest

from repro.alerts import WindowSpec
from repro.alerts import batch
from repro.alerts.batch import code_events, reduce_columns
from repro.alerts.windows import WindowAggregate
from repro.obs import unpack_order


def _loop_unpack(slots, lanes, mins, key_vocab, closed_at):
    """The unpack as it stood before the vectorised order, frozen."""
    out = []
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
    for a in out:
        a.closed_at_watermark = closed_at
    return out


def _first_seen(events):
    """The events' columns with keys coded in first-seen order, as
    ``scan_lanes`` codes them."""
    codes = {}
    for k, _, _ in events:
        codes.setdefault(k, len(codes))
    return (np.asarray([e[1] for e in events], np.float64),
            np.asarray([codes[e[0]] for e in events], np.int64),
            np.asarray([e[2] for e in events], np.float64), list(codes))


def _run(monkeypatch, columns, spec, *, with_min, closed_at):
    """-> (reduce_columns' records, the frozen loop's records over the
    same slots and lanes, the change in ``unpack_order()``)."""
    seen = {}
    pack, lane = batch.pack_columns, batch._kernel_lane

    def spy_pack(*a, **kw):
        out = pack(*a, **kw)
        seen["slots"] = list(out[2])
        return out

    def spy_lane(*a, **kw):
        out = lane(*a, **kw)
        seen["mins" if kw.get("negate") else "lanes"] = out
        return out

    monkeypatch.setattr(batch, "pack_columns", spy_pack)
    monkeypatch.setattr(batch, "_kernel_lane", spy_lane)
    kw = {} if closed_at is None else {"closed_at": closed_at}
    before = unpack_order()
    got = reduce_columns(*columns, spec, interpret=True,
                         with_min=with_min, **kw)
    after = unpack_order()
    if not seen.get("slots"):
        want = []
    else:
        mins = -seen["mins"][:, 3] if with_min else None
        want = _loop_unpack(seen["slots"], seen["lanes"], mins, columns[3],
                            0.0 if closed_at is None else closed_at)
    return got, want, {k: after[k] - before[k] for k in after}


TUMBLING = WindowSpec(kind="tumbling", size_s=60.0)
ODD_KEYS = ["b", "a", "a\x00", "é", "Z", "\udcff"]


def _grid(keys, ts):
    return [(k, t, float(1 + i % 7) * 0.1 + 1.0)
            for i, (t, k) in enumerate((t, k) for t in ts for k in keys)]


CASES = {
    # fixed windows over a sorted vocabulary come in record order
    "tumbling_sorted": (TUMBLING, _grid(["news", "ops", "sports"],
                                        [5.0, 70.0, 130.5]),
                        "sorted_vocab", True, None, "slot"),
    "tumbling_first_seen": (TUMBLING, _grid(["sports", "news", "ops"],
                                            [5.0, 70.0, 130.5]),
                            "first_seen", False, 123.5, "sorted"),
    "sliding_5_over_10": (WindowSpec(kind="sliding", size_s=10.0,
                                     slide_s=5.0),
                          _grid(["x", "w"], [0.0, 4.9, 5.0, 12.5, 33.3]),
                          "sorted_vocab", True, 7.0, "slot"),
    "sliding_0.3_over_7.5": (WindowSpec(kind="sliding", size_s=7.5,
                                        slide_s=0.3),
                             _grid(["k2", "k10", "k1"],
                                   [0.1, 0.7, 3.3, 9.05, 17.2]),
                             "first_seen", True, None, "sorted"),
    # a at 0 and exactly the gap later share one session, ending after
    # b's: b's session ends first, so the slots are reordered
    "session_exact_gap": (WindowSpec(kind="session", gap_s=10.0),
                          [("a", 0.0, 1.0), ("b", 5.0, 2.0),
                           ("a", 10.0, 3.0)],
                          "sorted_vocab", True, 50.0, "sorted"),
    # 8.0 bridges 0.0 and 15.0 into one session of c; d starts later
    "session_bridging_event": (WindowSpec(kind="session", gap_s=10.0),
                               [("c", 0.0, 4.0), ("c", 15.0, 5.0),
                                ("d", 40.0, 1.5), ("c", 8.0, 6.0)],
                               "first_seen", False, None, "slot"),
    "odd_keys_first_seen": (TUMBLING, _grid(ODD_KEYS, [1.0, 61.0]),
                            "first_seen", True, None, "sorted"),
    "odd_keys_sorted": (TUMBLING, _grid(ODD_KEYS, [1.0, 61.0]),
                        "sorted_vocab", False, 2.5, "slot"),
    "one_slot": (TUMBLING, [("solo", 3.0, 2.25), ("solo", 4.0, -1.0)],
                 "first_seen", True, 9.0, "slot"),
    "empty": (TUMBLING, [], "sorted_vocab", True, None, None),
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_unpack_equals_the_loop_and_sort(monkeypatch, name):
    spec, events, vocab, with_min, closed_at, path = CASES[name]
    if not events:
        columns = (np.empty(0), np.empty(0, np.int64), np.empty(0), [])
    elif vocab == "first_seen":
        columns = _first_seen(events)
    else:
        columns = code_events(events)
    got, want, counted = _run(monkeypatch, columns, spec,
                              with_min=with_min, closed_at=closed_at)
    assert [repr(a) for a in got] == [repr(a) for a in want]
    assert all(type(a.count) is int for a in got)
    assert [(a.window_end, a.key) for a in got] == sorted(
        (a.window_end, a.key) for a in got)
    assert len(got) == len({(a.key, a.window_start) for a in got})
    if path is None:
        assert got == [] and counted == {"slot": 0, "sorted": 0}
    else:
        assert got and counted == {"slot": int(path == "slot"),
                                   "sorted": int(path == "sorted")}


def test_unpack_equals_the_loop_on_many_random_slots(monkeypatch):
    """Hundreds of slots from a first-seen vocabulary of random keys,
    sliding windows, non-integer values: the same records in the same
    order as the loop, minima included."""
    rng = np.random.default_rng(2 ** 32 + 18)
    keys = [f"auction-{int(x)}" for x in rng.integers(0, 10 ** 6, 150)]
    n = 1200
    events = [(keys[int(i)], float(t), float(v)) for i, t, v in zip(
        rng.integers(0, len(keys), n), rng.uniform(0.0, 40.0, n),
        rng.normal(50.0, 20.0, n))]
    spec = WindowSpec(kind="sliding", size_s=10.0, slide_s=5.0)
    got, want, counted = _run(monkeypatch, _first_seen(events), spec,
                              with_min=True, closed_at=1e9)
    assert len(got) > 300
    assert [repr(a) for a in got] == [repr(a) for a in want]
    assert counted == {"slot": 0, "sorted": 1}
