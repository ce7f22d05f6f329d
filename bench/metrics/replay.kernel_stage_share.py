"""Share of the replay window's wall time in the replay engine's
``kernel`` stage (``StageProfiler("replay")``, host clock)."""


def read(record):
    stages = getattr(record, "stages", None)
    if not stages or stages.get("kernel", 0.0) <= 0.0:
        return None
    return 100.0 * stages["kernel"] / record.window_s
