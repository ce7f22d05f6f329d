"""Share of the replay window's wall time in the replay engine's
``pack_events.assign`` sub-stage (window assignment, expansion and the
column stack in ``pack_columns``), from the program's ring of recent
passes (``bench/device/program_spans.py``)."""
from bench.device.program_spans import replay_share


def read(record):
    return replay_share(record, "pack_events.assign")
