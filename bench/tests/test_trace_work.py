"""The trace reduction and the kernel's work, on a small trace laid out
as the TPU v5e's profiler lays it out (one ``/device:TPU:0`` plane with
``XLA Modules`` and ``XLA Ops`` lines, the harness's ``bench.*`` host
annotations on ``/host:CPU``)."""
import math

import numpy as np
import pytest

from bench.device.trace_reduce import Event, Plane, summarize, union
from bench.work import device_peak, window_reduce_work

MS = 1e6  # ns


def _trace():
    host = Plane("/host:CPU", {"python3": [
        Event("bench.window", 0, 100 * MS),
        Event("bench.replay_log", 0, 50 * MS),
        Event("bench.replay_log", 50 * MS, 100 * MS),
        Event("unrelated", 0, 100 * MS)]})
    dev = Plane("/device:TPU:0", {
        "XLA Modules": [
            Event("jit_window_reduce_fwd(123)", 10 * MS, 20 * MS),
            Event("jit_window_reduce_fwd(123)", 60 * MS, 75 * MS),
            Event("jit_other(9)", 90 * MS, 95 * MS)],
        "XLA Ops": [
            Event("%pad.0 = f32[8] pad(...)", 10 * MS, 12 * MS),
            Event("%window_reduce_fwd.1 = f32[4,8] custom-call(...)",
                  11 * MS, 20 * MS),
            Event("%window_reduce_fwd.1 = f32[4,8] custom-call(...)",
                  60 * MS, 75 * MS),
            Event("%other = f32[] add(...)", 90 * MS, 95 * MS),
            Event("%late = f32[] add(...)", 120 * MS, 130 * MS)]})
    sparse = Plane("/device:TPU:0 SparseCore 0", {"XLA Ops": [
        Event("%x = f32[] add(...)", 0, 100 * MS)]})
    return [host, dev, sparse]


def test_busy_is_the_union_of_ops_inside_the_window():
    s = summarize(_trace(), ["window_reduce_fwd"])
    assert s.chips == 1
    assert s.window_s == pytest.approx(0.1)
    # [10, 20] (overlapping pad and kernel) + [60, 75] + [90, 95]
    assert s.busy_s == pytest.approx(0.030)
    assert s.idle_share == pytest.approx(0.7)


def test_kernel_time_counts_every_call_of_the_jitted_name():
    s = summarize(_trace(), ["window_reduce_fwd"])
    assert s.kernel_s["window_reduce_fwd"] == pytest.approx(0.025)
    assert s.kernel_calls["window_reduce_fwd"] == 2


def test_device_ops_and_idle_gaps_are_named():
    s = summarize(_trace(), ["window_reduce_fwd"])
    assert s.device_ops[0] == ("%window_reduce_fwd.1", pytest.approx(0.024))
    # the longest gap, [20, 60], lies mostly under the first replay_log
    assert s.idle_gaps[0] == ("bench.replay_log", pytest.approx(0.040))
    assert sum(g for _, g in s.idle_gaps) == pytest.approx(0.070)


def test_a_trace_without_the_window_or_the_device_is_refused():
    host, dev, _ = _trace()
    with pytest.raises(ValueError):
        summarize([dev])
    with pytest.raises(ValueError):
        summarize([host])


def test_union_merges_overlaps():
    assert union([(5, 7), (0, 2), (1, 3), (3, 4), (6, 6)]) == \
        [(0, 4), (5, 7)]


def test_least_time_is_bound_by_memory():
    peak = device_peak("TPU v5 lite")
    w = window_reduce_work(memberships=1_000_000, slots=50_000)
    assert w.bytes == 8e6 + 1e6 and w.flops == 5e6
    assert w.least_s(peak) == pytest.approx(9e6 / 819e9)
    # the bytes bound the least time by two orders of magnitude and more
    assert (w.bytes / peak["hbm_bytes_per_s"]) / \
        (w.flops / peak["flops_per_s"]) > 400


def test_a_device_not_in_the_table_is_an_error():
    with pytest.raises(KeyError):
        device_peak("cpu")


@pytest.mark.parametrize("kind,size,slide", [("tumbling", 60.0, None),
                                             ("sliding", 10.0, 5.0),
                                             ("sliding", 300.0, 60.0)])
def test_memberships_and_slots_do_not_depend_on_the_packing(kind, size,
                                                            slide):
    """M and S from the data and the spec equal what both of the
    program's packers lay out, at any kernel block size."""
    from bench.refs.reduce import window_rows
    from repro.alerts import WindowSpec
    from repro.alerts.batch import pack_columns, pack_events

    rng = np.random.default_rng(7)
    ts = np.sort(rng.random(3000) * 900.0)
    keys = rng.integers(0, 13, ts.size)
    spec = {"kind": kind, "size_s": size}
    if slide:
        spec["slide_s"] = slide
    idx, starts = window_rows(ts, spec)
    slots = len({(k, s) for k, s in zip(keys[idx].tolist(),
                                       starts.tolist())})
    wspec = WindowSpec(kind=kind, size_s=size, slide_s=slide)
    vals, segs, col_slots = pack_columns(ts, keys, np.ones(ts.size), wspec)
    ev_vals, ev_segs, ev_slots = pack_events(
        [(str(k), t, 1.0) for k, t in zip(keys.tolist(), ts.tolist())],
        wspec)
    assert idx.size == vals.size == ev_vals.size
    assert slots == len(col_slots) == len(ev_slots)
    w = window_reduce_work(idx.size, slots)
    assert math.isclose(w.bytes, 8 * vals.size + 20 * len(col_slots))
