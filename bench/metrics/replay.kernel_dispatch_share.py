"""Share of the replay window's wall time in the replay engine's
``kernel.dispatch`` sub-stage (``ops.window_reduce`` calls, until each
returns), from the program's ring of recent passes
(``bench/device/program_spans.py``)."""
from bench.device.program_spans import replay_share


def read(record):
    return replay_share(record, "kernel.dispatch")
