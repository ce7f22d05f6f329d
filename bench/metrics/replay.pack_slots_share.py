"""Share of the replay window's wall time in the replay engine's
``pack_events.slots`` sub-stage (the Python slot list in
``pack_columns``), from the program's ring of recent passes
(``bench/device/program_spans.py``)."""
from bench.device.program_spans import replay_share


def read(record):
    return replay_share(record, "pack_events.slots")
