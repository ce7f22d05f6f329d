"""StageProfiler — always-on wall-clock timers for named pipeline stages.

A profiler instance rides the component that owns a chain of stages
(the ReplayEngine's batch-replay chain: decode -> pack_events -> kernel
-> unpack -> state_merge), each stage is wrapped in
``with profiler.stage(name):``, and ``snapshot()`` reports per-stage
call counts, total/mean/max milliseconds, and each stage's share of the
profiled total — the breakdown ``replay_status()`` and the
``replay_stage_*`` metrics surface.

Stages nest by name: a dotted stage (``kernel.wait``) is a sub-stage of
its prefix (``kernel``) and is timed inside it.  ``share`` is always a
share of the TOP-LEVEL stages' total, so the top-level shares add up to
1 and a sub-stage's share reads against the same total as its parent's.

Every pass does two more things, so that it can be placed on a
timeline and not only summed:

  device trace  while a profiler trace is active it opens a
                ``jax.profiler.TraceAnnotation`` named
                ``<profiler>.<stage>`` (``replay.pack_events.unique``),
                so the pass sits on the same timeline as the device ops.
                JAX is never imported from here: the annotation is
                resolved once JAX is loaded, and until then a pass
                opens none.
  recent passes it appends ``(profiler, stage, start, seconds)`` on
                ``time.perf_counter`` to one bounded process-wide ring,
                read with :func:`recent_passes`: the last
                ``RECENT_PASSES`` passes of every profiler, no ids, no
                sampling, no exporter.

A pass costs two ``perf_counter`` calls, one locked accumulate, one
deque append and, once JAX is loaded, a check for an active trace: on
the order of a microsecond with no trace active, so it stays on in
production paths.
"""
from __future__ import annotations

import collections
import sys
import threading
import time
from typing import Dict, List, Tuple

#: passes the process-wide ring keeps (all profilers together)
RECENT_PASSES = 1 << 15

Pass = Tuple[str, str, float, float]      # (profiler, stage, start, seconds)

_RING: "collections.deque[Pass]" = collections.deque(maxlen=RECENT_PASSES)
_perf = time.perf_counter
_annotation_cls = None


def _annotation_class():
    """``jax.profiler.TraceAnnotation`` while a profiler trace is
    active, else None; None until JAX is loaded, since observability
    code must not import JAX itself."""
    global _annotation_cls
    cls = _annotation_cls
    if cls is None:
        if "jax" not in sys.modules:
            return None
        from jax.profiler import TraceAnnotation
        cls = _annotation_cls = TraceAnnotation
    return cls if cls.is_enabled() else None


def trace_annotation(name: str):
    """An entered-on-demand host event named ``name`` for the active
    profiler trace, or None when no trace is active."""
    cls = _annotation_class()
    return None if cls is None else cls(name)


def recent_passes() -> List[Pass]:
    """The ring's passes, oldest first: ``(profiler, stage, start,
    seconds)`` with ``start`` on ``time.perf_counter``."""
    while True:
        try:
            return list(_RING)
        except RuntimeError:              # appended to while copied
            continue


class _Stage:
    __slots__ = ("calls", "total_s", "max_s", "last_s")

    def __init__(self):
        self.calls = 0
        self.total_s = 0.0
        self.max_s = 0.0
        self.last_s = 0.0


class _StageCtx:
    __slots__ = ("_prof", "_name", "_t0", "_ann")

    def __init__(self, prof: "StageProfiler", name: str):
        self._prof = prof
        self._name = name

    def __enter__(self):
        cls = _annotation_class()
        if cls is None:
            self._ann = None
        else:
            ann = self._ann = cls(self._prof._label(self._name))
            ann.__enter__()
        self._t0 = _perf()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        t0 = self._t0
        dt = _perf() - t0
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        self._prof._record(self._name, dt, t0)


class StageProfiler:
    def __init__(self, name: str = "profile"):
        self.name = name
        self._lock = threading.Lock()
        self._stages: Dict[str, _Stage] = {}
        self._labels: Dict[str, str] = {}

    def stage(self, name: str) -> _StageCtx:
        """Time one pass through stage ``name`` (context manager); a
        dotted name is a sub-stage of its prefix."""
        return _StageCtx(self, name)

    def record(self, name: str, seconds: float) -> None:
        """Fold an externally-timed duration into stage ``name``, as a
        pass that ended now."""
        self._record(name, seconds, _perf() - seconds)

    def _label(self, name: str) -> str:
        label = self._labels.get(name)
        if label is None:
            label = self._labels[name] = f"{self.name}.{name}"
        return label

    def _record(self, name: str, dt: float, t0: float) -> None:
        _RING.append((self.name, name, t0, dt))
        with self._lock:
            st = self._stages.get(name)
            if st is None:
                st = self._stages[name] = _Stage()
            st.calls += 1
            st.total_s += dt
            st.last_s = dt
            if dt > st.max_s:
                st.max_s = dt

    def reset(self) -> None:
        with self._lock:
            self._stages.clear()

    def snapshot(self) -> dict:
        """{stage: {calls, total_ms, mean_ms, max_ms, last_ms, share}}
        — ``share`` is the stage's fraction of the top-level stages'
        total; the top-level shares add up to 1."""
        with self._lock:
            total = sum(s.total_s for name, s in self._stages.items()
                        if "." not in name)
            out = {}
            for name, s in sorted(self._stages.items()):
                out[name] = {
                    "calls": s.calls,
                    "total_ms": s.total_s * 1e3,
                    "mean_ms": (s.total_s / s.calls) * 1e3 if s.calls else 0.0,
                    "max_ms": s.max_s * 1e3,
                    "last_ms": s.last_s * 1e3,
                    "share": (s.total_s / total) if total > 0 else 0.0,
                }
            return out


__all__ = ["RECENT_PASSES", "StageProfiler", "recent_passes",
           "trace_annotation"]
