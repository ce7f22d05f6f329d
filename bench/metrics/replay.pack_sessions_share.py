"""Share of the replay window's wall time in the replay engine's
``pack_events.sessions`` sub-stage (the session layout in
``pack_columns``: the sort by key and event time, the cut at idle gaps,
the seg ids and each session's start and end), from the program's ring
of recent passes (``bench/device/program_spans.py``).

A program with the session layout whose window replayed only fixed
windows spent none of it there, and reads 0; a program without the
session layout (no ``repro.obs.pack_sessions``) reads None."""
from bench.device.program_spans import replay_share


def read(record):
    share = replay_share(record, "pack_events.sessions")
    if share is None and replay_share(record, "pack_events") is not None:
        try:
            from repro.obs import pack_sessions  # noqa: F401
        except ImportError:
            return None
        return 0.0
    return share
