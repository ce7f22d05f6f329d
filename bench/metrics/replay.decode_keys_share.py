"""Share of the replay window's wall time in the replay engine's
``decode.keys`` sub-stage (``ColumnarEventLog.scan_lanes``' second pass:
the merge of the blocks' key dictionaries into one vocabulary, the remap
of the key codes and the lanes' concatenation), from the program's ring
of recent passes (``bench/device/program_spans.py``).  A program whose
``scan_lanes`` times no sub-stages reads None."""
from bench.device.program_spans import replay_share


def read(record):
    return replay_share(record, "decode.keys")
