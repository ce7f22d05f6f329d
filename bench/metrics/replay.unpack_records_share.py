"""Share of the replay window's wall time in the replay engine's
``unpack.records`` sub-stage (``reduce_columns``' records: the lanes
gathered in record order, their columns converted with ``.tolist()``,
one ``WindowAggregate`` built a slot), from the program's ring of recent
passes (``bench/device/program_spans.py``).  A program whose unpack
times no sub-stages reads None."""
from bench.device.program_spans import replay_share


def read(record):
    return replay_share(record, "unpack.records")
