"""Share of the replay window's wall time in the replay engine's
``decode.blocks`` sub-stage (``ColumnarEventLog.scan_lanes``' first
pass: segment files, block headers, checksums, and the event-time, value
and key-code lanes), from the program's ring of recent passes
(``bench/device/program_spans.py``).  A program whose ``scan_lanes``
times no sub-stages reads None."""
from bench.device.program_spans import replay_share


def read(record):
    return replay_share(record, "decode.blocks")
