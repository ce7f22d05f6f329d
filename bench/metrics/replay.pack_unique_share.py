"""Share of the replay window's wall time in the replay engine's
``pack_events.unique`` sub-stage (the (start, key) ``np.unique`` and its
inverse in ``pack_columns``), from the program's ring of recent passes
(``bench/device/program_spans.py``)."""
from bench.device.program_spans import replay_share


def read(record):
    return replay_share(record, "pack_events.unique")
