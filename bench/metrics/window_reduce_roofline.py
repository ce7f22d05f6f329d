"""``window_reduce``'s share of its roofline over the replays of the
traced window: the least time the chip needs for the replays' work
(``bench/work.py``: 8 M + 20 S bytes, 5 M operations; memory-bound by
about 400x) over the device time of every ``window_reduce_fwd`` call
in the trace (both launches of the min path count)."""


def read(record):
    trace = getattr(record, "trace", None)
    work = getattr(record, "work", None)
    if trace is None or not work:
        return None
    kernel_s = trace.kernel_s.get("window_reduce_fwd", 0.0)
    if kernel_s <= 0.0:
        return None
    from bench.work import device_peak
    peak = device_peak(record.device_kind)
    return 100.0 * sum(w.least_s(peak) for w in work) / kernel_s
