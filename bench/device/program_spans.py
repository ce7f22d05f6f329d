"""Per-layer readings from the program's own stage spans.

The program's ``StageProfiler`` keeps every pass it times in a bounded
process-wide ring (``repro.obs.profiler.recent_passes()``: profiler,
stage, start and seconds on ``time.perf_counter``, the harness's
clock).  A stage's share of a replay window is the time its passes
spent inside the window, over the window:

- the window ends at the end of the newest top-level pass of the
  profiler (the replay window closes when the last ``replay_log``
  returns), and starts ``record.window_s`` before that;
- each pass is clipped to the window, so the warm-up outside it counts
  nothing and a pass that straddles an edge counts its inside part;
- when the ring's oldest pass starts inside the window, the ring has
  wrapped and dropped passes of the window: the reading is None.

A program without the ring, or a stage that never ran, reads None.
"""
from __future__ import annotations

from typing import Optional, Sequence, Tuple

Pass = Tuple[str, str, float, float]     # (profiler, stage, start, seconds)


def window_share(passes: Sequence[Pass], window_s: float, stage: str,
                 profiler: str = "replay") -> Optional[float]:
    """Percent of the window that ``stage`` of ``profiler`` took."""
    mine = [p for p in passes if p[0] == profiler]
    top = [p for p in mine if "." not in p[1]]
    if not top or window_s <= 0.0 or not any(p[1] == stage for p in mine):
        return None
    end = max(start + secs for _, _, start, secs in top)
    begin = end - window_s
    if passes[0][2] > begin:            # the ring wrapped inside the window
        return None
    inside = sum(max(0.0, min(start + secs, end) - max(start, begin))
                 for _, name, start, secs in mine if name == stage)
    return 100.0 * inside / window_s


def replay_share(record, stage: str) -> Optional[float]:
    """``window_share`` of the replay engine's ``stage`` over the
    record's window, read from the program's ring."""
    try:
        from repro.obs.profiler import recent_passes
    except ImportError:                 # a program without the ring
        return None
    window_s = getattr(record, "window_s", None)
    passes = recent_passes()
    if not window_s or not passes:
        return None
    return window_share(passes, window_s, stage)
