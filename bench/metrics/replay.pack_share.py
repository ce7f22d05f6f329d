"""Share of the replay window's wall time in the replay engine's
``pack_events`` stage (``StageProfiler("replay")``, host clock)."""


def read(record):
    stages = getattr(record, "stages", None)
    if not stages or stages.get("pack_events", 0.0) <= 0.0:
        return None
    return 100.0 * stages["pack_events"] / record.window_s
