"""Share of the traced replay window in which no operation ran on the
device: 1 - union of the device's op intervals / window."""


def read(record):
    trace = getattr(record, "trace", None)
    if trace is None or getattr(record, "route", None) != "replay":
        return None
    return 100.0 * trace.idle_share
