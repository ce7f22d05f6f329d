"""Share of the replay window's wall time in the replay engine's
``kernel.wait`` sub-stage (``block_until_ready`` on each launch's
result), from the program's ring of recent passes
(``bench/device/program_spans.py``)."""
from bench.device.program_spans import replay_share


def read(record):
    return replay_share(record, "kernel.wait")
