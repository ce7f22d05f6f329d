"""Share of the replay window's wall time in the replay engine's
``unpack.order`` sub-stage (``reduce_columns``' record order: the slots'
codes and ends as arrays, the key vocabulary ranked by ``str`` order,
one ``np.lexsort``), from the program's ring of recent passes
(``bench/device/program_spans.py``).  A program whose unpack times no
sub-stages reads None."""
from bench.device.program_spans import replay_share


def read(record):
    return replay_share(record, "unpack.order")
