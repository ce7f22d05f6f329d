"""Trace spans — reconstruct one record's journey across every plane.

A *trace* is the tree of timed spans a single unit of work (one
connector fetch, one scheduler tick, one replay pass) produced, joined
by ``trace_id``.  The pipeline instruments connector fetch -> dedup/
enrich -> store append -> delivery emit synchronously, stamps the
``trace_id`` onto each accepted document (``doc["trace"]``), and the
delivery layer's :class:`TracingSink` picks the id back up when the
batched/dispatched write finally lands — so a document's path through
ingest, pipeline, store, and delivery reads back as one trace even
though delivery is asynchronous.

Design constraints, in order:

  cheap off      ``sample_rate=0.0`` (the default) short-circuits
                 ``span()`` to a shared no-op context manager — no
                 allocation, no clock reads, no behaviour change.
  cheap on       a sampled span is two ``perf_counter`` calls plus one
                 append into a bounded deque (the flight recorder), and,
                 once JAX is loaded, one ``jax.profiler.TraceAnnotation``
                 of the span's name, so sampled spans (``ingest.fetch``,
                 ``pipeline.process``, ``replay.late_events``,
                 ``query.cold_scan``) share a profiler trace's timeline
                 with the device ops.
  deterministic  sampling uses a seeded RNG and ids come from a
                 counter, so a traced replay is reproducible.

Clocks: a span's ``start`` is read from the tracer's ``clock``, wall
time (``time.time``) by default, so exported spans line up across
processes; durations are measured on ``time.perf_counter``.

The flight recorder is a ring of the last ``capacity`` finished spans
(``spans()``, ``trace(trace_id)``, ``traces()``).  For durability,
attach a :class:`TraceExporter`: every finished span is appended as one
JSONL line to a size-rolled file set (the EventLog idiom — append-only
segments, roll at ``max_bytes``), so ``trace_id`` greps work on disk
after the ring has wrapped.
"""
from __future__ import annotations

import collections
import itertools
import json
import os
import random
import threading
import time
from typing import Dict, List, Optional

from repro.delivery.base import Sink, SinkClosedError
from repro.obs.profiler import trace_annotation

_perf = time.perf_counter


class Span:
    """One timed operation inside a trace, and its own context manager
    (one allocation per span on the hot path).  ``set(key, value)``
    attaches attributes; ``duration_ms`` is filled when the context
    exits.  Ids are stored as counter integers and formatted lazily —
    ``span_id``/``parent_id`` are properties."""

    __slots__ = ("_tracer", "trace_id", "_sid", "_psid", "name", "start",
                 "duration_ms", "attrs", "error", "events", "_t0",
                 "_onstack", "_ann")

    sampled = True

    def __init__(self, tracer: "Tracer", trace_id: str, sid,
                 psid, name: str, start: float,
                 attrs: Optional[dict], onstack: bool = True):
        self._tracer = tracer
        self.trace_id = trace_id
        self._sid = sid                   # int from the counter, or a
        self._psid = psid                 # pre-formatted str (event views)
        self.name = name
        self.start = start
        self.duration_ms: float = 0.0
        self.attrs: Dict[str, object] = attrs if attrs is not None else {}
        self.error: Optional[str] = None
        self.events = None                # [(name, t0, dur_s, attrs, err)]
        self._onstack = onstack

    @property
    def span_id(self) -> str:
        sid = self._sid
        return sid if sid.__class__ is str else f"s{sid:x}"

    @property
    def parent_id(self) -> Optional[str]:
        psid = self._psid
        if psid is None:
            return None
        return psid if psid.__class__ is str else f"s{psid:x}"

    def set(self, key: str, value) -> None:
        self.attrs[key] = value

    def child(self, name: str, attrs: Optional[dict] = None) -> "Span":
        """A direct child span that SKIPS the thread-local stack — a
        cheap path for leaf work with no deeper ``tracer.span`` nesting
        inside it."""
        tracer = self._tracer
        return Span(tracer, self.trace_id, next(tracer._ids), self._sid,
                    name, tracer.clock(), attrs, onstack=False)

    def event(self, name: str, t0: float, attrs: Optional[dict] = None,
              error: Optional[str] = None) -> None:
        """Record a completed sub-operation as a span EVENT (the OTel
        idiom): one tuple appended to this span, materialized as a child
        span by the flight-recorder reads and the exporter.  ~5x cheaper
        than a child Span — the hot ingest loop uses this for
        pipeline.process / store.append / delivery.emit.  ``t0`` is the
        ``time.perf_counter()`` value taken when the operation started
        (no wall-clock read: the start is derived from this span's)."""
        ev = (name, t0, _perf() - t0, attrs, error)
        if self.events is None:
            self.events = [ev]
        else:
            self.events.append(ev)

    def as_dict(self) -> dict:
        out = {"trace_id": self.trace_id, "span_id": self.span_id,
               "parent_id": self.parent_id, "name": self.name,
               "start": self.start, "duration_ms": self.duration_ms}
        if self.attrs:
            out["attrs"] = dict(self.attrs)
        if self.error is not None:
            out["error"] = self.error
        return out

    def __enter__(self) -> "Span":
        if self._onstack:
            local = self._tracer._local
            try:
                local.stack.append(self)
            except AttributeError:
                local.stack = [self]
        ann = self._ann = trace_annotation(self.name)
        if ann is not None:
            ann.__enter__()
        self._t0 = _perf()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.duration_ms = (_perf() - self._t0) * 1e3
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)
        tracer = self._tracer
        if self._onstack:
            stack = tracer._local.stack
            if stack and stack[-1] is self:
                stack.pop()
            elif self in stack:                   # unbalanced exit: recover
                stack.remove(self)
        if exc is not None:
            self.error = f"{type(exc).__name__}: {exc}"
        tracer._spans.append(self)                # deque append: thread-safe
        events = self.events
        tracer.finished_spans += 1 + (len(events) if events else 0)
        exporter = tracer.exporter
        if exporter is not None:
            try:
                exporter.append(self.as_dict())
                if events:
                    for view in _event_spans(self):
                        exporter.append(view.as_dict())
            except Exception:
                pass            # durability is best-effort; tracing is not


def _event_spans(span: Span) -> List["Span"]:
    """Materialize a span's recorded events as child-span views.  Ids
    are derived (``<parent_id>.<n>``) so repeated reads are stable; the
    wall-clock start is reconstructed from the parent's perf-counter
    base, so no clock was read on the hot path."""
    out: List[Span] = []
    pid = span.span_id
    for i, (name, t0, dur, attrs, error) in enumerate(span.events):
        view = Span(span._tracer, span.trace_id, f"{pid}.{i + 1}", pid,
                    name, span.start + (t0 - span._t0), attrs,
                    onstack=False)
        view.duration_ms = dur * 1e3
        view.error = error
        out.append(view)
    return out


class _NoopSpan:
    """Placeholder for unsampled work: carries no ids, records nothing,
    but still nests correctly (children of an unsampled root stay
    unsampled)."""

    __slots__ = ()
    trace_id = None
    span_id = None
    parent_id = None
    sampled = False

    def set(self, key: str, value) -> None:
        pass

    def child(self, name: str, attrs: Optional[dict] = None):
        return _DISABLED_CTX

    def event(self, name: str, t0: float, attrs: Optional[dict] = None,
              error: Optional[str] = None) -> None:
        pass


_NOOP_SPAN = _NoopSpan()


class _NoopCtx:
    """Context manager for UNSAMPLED work: records nothing but still
    pushes the noop span so descendants inherit the unsampled decision."""

    __slots__ = ("_tracer",)

    def __init__(self, tracer: "Tracer"):
        self._tracer = tracer

    def __enter__(self):
        local = self._tracer._local
        try:
            local.stack.append(_NOOP_SPAN)
        except AttributeError:
            local.stack = [_NOOP_SPAN]
        return _NOOP_SPAN

    def __exit__(self, exc_type, exc, tb) -> None:
        stack = self._tracer._local.stack
        if stack and stack[-1] is _NOOP_SPAN:
            stack.pop()


class _DisabledCtx:
    """Shared zero-cost context for a disabled tracer."""

    __slots__ = ()

    def __enter__(self):
        return _NOOP_SPAN

    def __exit__(self, exc_type, exc, tb) -> None:
        pass


_DISABLED_CTX = _DisabledCtx()


class Tracer:
    """Span factory + flight recorder; see the module docstring.

    ``span(name)`` opens a child of the calling thread's current span,
    or a new root (sampling decision) when there is none.  Pass
    ``trace_id=`` to graft onto a known trace from another thread or a
    record that carried the id (delivery handoff, replay)."""

    def __init__(self, *, sample_rate: float = 0.0, capacity: int = 4096,
                 seed: int = 0, exporter: Optional["TraceExporter"] = None,
                 clock=time.time):
        if not 0.0 <= sample_rate <= 1.0:
            raise ValueError("sample_rate must be in [0, 1]")
        self.sample_rate = float(sample_rate)
        self.capacity = capacity
        self.exporter = exporter
        self.clock = clock
        self._spans: collections.Deque[Span] = collections.deque(
            maxlen=capacity)
        self._rng = random.Random(seed)
        self._ids = itertools.count(1)
        self._local = threading.local()
        self.started_traces = 0
        self.sampled_traces = 0
        self.finished_spans = 0

    @property
    def enabled(self) -> bool:
        return self.sample_rate > 0.0

    # ---- span lifecycle ----------------------------------------------------
    def span(self, name: str, trace_id: Optional[str] = None,
             attrs: Optional[dict] = None, stack: bool = True):
        """NOTE: a literal ``attrs`` dict is adopted, not copied — pass a
        fresh dict per call (every in-tree call site does).  Pass
        ``stack=False`` for a root whose body never opens nested
        ``tracer.span`` contexts (children via ``.child``/``.event``
        only): it skips the thread-local stack entirely."""
        if self.sample_rate == 0.0:
            return _DISABLED_CTX
        psid = None
        if trace_id is None and stack:
            st = getattr(self._local, "stack", None)
            parent = st[-1] if st else None
            if parent is not None:
                if not parent.sampled:
                    return _NoopCtx(self)
                trace_id = parent.trace_id
                psid = parent._sid
        if trace_id is None:                      # new root: sample here
            # stats/RNG updates ride the GIL (itertools.count is atomic;
            # the counters are monitoring-only) — no lock on the hot path
            self.started_traces += 1
            if (self.sample_rate < 1.0
                    and self._rng.random() >= self.sample_rate):
                return _NoopCtx(self) if stack else _DISABLED_CTX
            self.sampled_traces += 1
            trace_id = f"t{next(self._ids):08x}"
        return Span(self, trace_id, next(self._ids), psid, name,
                    self.clock(), attrs, onstack=stack)

    def record_span(self, name: str, trace_id: str, start: float,
                    duration_ms: float, attrs: Optional[dict] = None,
                    error: Optional[str] = None) -> None:
        """Fast path for pre-timed work: append one already-finished
        root-level span straight to the flight recorder — no context
        manager, no thread-local stack, no extra clock reads, and no
        Span allocation (a compact tuple rides the ring; reads
        materialize it).  Used where one measured operation fans out to
        several traces (a delivery batch carrying many trace ids)."""
        rec = (name, trace_id, next(self._ids), start, duration_ms,
               attrs, error)
        self._spans.append(rec)
        self.finished_spans += 1
        if self.exporter is not None:
            try:
                self.exporter.append(self._record_view(rec).as_dict())
            except Exception:
                pass

    def _record_view(self, rec) -> Span:
        """Materialize one compact record_span tuple as a Span view."""
        name, trace_id, sid, start, duration_ms, attrs, error = rec
        view = Span(self, trace_id, sid, None, name, start, attrs,
                    onstack=False)
        view.duration_ms = duration_ms
        view.error = error
        return view

    def current_trace_id(self) -> Optional[str]:
        """The calling thread's active trace id (None when unsampled or
        no span is open) — what gets stamped onto records."""
        stack = getattr(self._local, "stack", None)
        return stack[-1].trace_id if stack else None

    # ---- flight recorder reads ---------------------------------------------
    def spans(self) -> List[Span]:
        """Every retained span, with span events and compact pre-timed
        records materialized (read path only — the ring itself stores
        one entry per real span)."""
        out: List[Span] = []
        for s in self._spans:
            if s.__class__ is not Span:           # record_span tuple
                out.append(self._record_view(s))
                continue
            out.append(s)
            if s.events:
                out.extend(_event_spans(s))
        return out

    def trace(self, trace_id: str) -> List[Span]:
        """Every retained span of one trace, in start order."""
        out = [s for s in self.spans() if s.trace_id == trace_id]
        out.sort(key=lambda s: s.start)
        return out

    def traces(self) -> Dict[str, List[Span]]:
        out: Dict[str, List[Span]] = {}
        for s in self.spans():
            out.setdefault(s.trace_id, []).append(s)
        for spans in out.values():
            spans.sort(key=lambda s: s.start)
        return out

    def status(self) -> dict:
        return {"sample_rate": self.sample_rate,
                "started_traces": self.started_traces,
                "sampled_traces": self.sampled_traces,
                "finished_spans": self.finished_spans,
                "flight_spans": len(self._spans),
                "capacity": self.capacity}


class TraceExporter:
    """Append-only JSONL span export with size-based file roll (the
    EventLog idiom scaled down): spans land in ``<dir>/spans-<n>.jsonl``;
    when the active file passes ``max_bytes`` it is closed and the next
    one opened.  ``scan()`` reads every exported span back in order."""

    def __init__(self, dir_path: str, *, max_bytes: int = 4 << 20):
        self.dir = dir_path
        self.max_bytes = max_bytes
        os.makedirs(dir_path, exist_ok=True)
        existing = sorted(f for f in os.listdir(dir_path)
                          if f.startswith("spans-") and f.endswith(".jsonl"))
        self._index = len(existing)
        self._lock = threading.Lock()
        self._fh = None
        self._bytes = 0
        self.exported = 0
        self.torn_skipped = 0

    def _open_next(self) -> None:
        if self._fh is not None:
            self._fh.close()
        path = os.path.join(self.dir, f"spans-{self._index:05d}.jsonl")
        self._index += 1
        self._fh = open(path, "a", encoding="utf-8")
        self._bytes = 0

    def append(self, span_dict: dict) -> None:
        line = json.dumps(span_dict, sort_keys=True, default=repr) + "\n"
        with self._lock:
            if self._fh is None or self._bytes >= self.max_bytes:
                self._open_next()
            self._fh.write(line)
            self._bytes += len(line)
            self.exported += 1

    def flush(self) -> None:
        with self._lock:
            if self._fh is not None:
                self._fh.flush()

    def scan(self):
        """Yield every exported span dict, file order then line order.

        Crash tolerance (the store plane's standard): a process dying
        mid-append leaves at most one torn line, and — because reopen
        always starts a NEW file — only ever as a file's FINAL line.
        A final line that fails to decode is skipped (counted in
        ``torn_skipped``); a corrupt line anywhere else is real damage
        and still raises."""
        self.flush()
        for fname in sorted(os.listdir(self.dir)):
            if not (fname.startswith("spans-") and fname.endswith(".jsonl")):
                continue
            with open(os.path.join(self.dir, fname), encoding="utf-8") as fh:
                lines = fh.read().splitlines()
            while lines and not lines[-1].strip():
                lines.pop()
            for i, line in enumerate(lines):
                line = line.strip()
                if not line:
                    continue
                try:
                    yield json.loads(line)
                except ValueError:
                    if i == len(lines) - 1:       # torn tail: crash artifact
                        self.torn_skipped += 1
                        continue
                    raise

    def close(self) -> None:
        with self._lock:
            if self._fh is not None:
                self._fh.close()
                self._fh = None

    def __enter__(self):
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.close()


class TracingSink(Sink):
    """Sink wrapper that records a ``delivery.write`` span per traced
    batch at the moment the wrapped sink actually accepts (or rejects)
    it.  Sits INSIDE the retry envelope (``Retrying(Tracing(terminal))``)
    so every attempt — first try, backoff retry, dispatcher-thread
    write, replay — shows up, carrying the trace ids the records were
    stamped with at ingest.  Records without a trace id pass through
    silently; with the tracer disabled the wrapper is never mounted."""

    def __init__(self, inner: Sink, tracer: Tracer, *,
                 name: Optional[str] = None):
        super().__init__(name or inner.name)
        self.inner = inner
        self.tracer = tracer

    @staticmethod
    def _trace_ids(batch) -> Dict[str, int]:
        ids: Dict[str, int] = {}
        for record in batch:
            cls = record.__class__
            if cls is tuple or cls is list:
                doc = record[1] if len(record) == 2 else None
            else:
                doc = record if cls is dict else None
            if doc is not None:
                tid = doc.get("trace")
                if tid:
                    ids[tid] = ids.get(tid, 0) + 1
        return ids

    def emit(self, batch) -> None:
        # overrides the base accounting entirely: this wrapper is
        # TRANSPARENT — no second copy of the batch, no second counter
        # set, no second health state (``healthy`` delegates to the
        # terminal, so retry/health-flip semantics are unchanged)
        if self.closed:
            raise SinkClosedError(f"sink {self.name!r} is closed")
        tracer = self.tracer
        if not tracer.enabled:
            self.inner.emit(batch)
            return
        if len(batch) == 1:             # hot shape: one record per write
            record = batch[0]
            cls = record.__class__
            if cls is tuple or cls is list:
                doc = record[1] if len(record) == 2 else None
            else:
                doc = record if cls is dict else None
            tid = doc.get("trace") if doc is not None else None
            if not tid:
                self.inner.emit(batch)
                return
            start = tracer.clock()
            t0 = time.perf_counter()
            err = None
            try:
                self.inner.emit(batch)
            except Exception as exc:
                err = f"{type(exc).__name__}: {exc}"
                raise
            finally:
                tracer.record_span(
                    "delivery.write", tid, start,
                    (time.perf_counter() - t0) * 1e3,
                    {"backend": self.name, "records": 1, "batch": 1}, err)
            return
        ids = self._trace_ids(batch)
        if not ids:
            self.inner.emit(batch)
            return
        start = tracer.clock()
        t0 = time.perf_counter()
        err = None
        try:
            self.inner.emit(batch)
        except Exception as exc:
            err = f"{type(exc).__name__}: {exc}"
            raise
        finally:
            # one measured write fans out to every trace riding the
            # batch: a pre-timed span per trace id, sharing the clock
            dt = (time.perf_counter() - t0) * 1e3
            n_batch = len(batch)
            backend = self.name
            record = tracer.record_span
            for tid, n in ids.items():
                record("delivery.write", tid, start, dt,
                       {"backend": backend, "records": n,
                        "batch": n_batch}, err)

    @property
    def healthy(self) -> bool:
        return self.inner.healthy

    def health(self) -> dict:
        return self.inner.health()

    def flush(self) -> None:
        super().flush()
        self.inner.flush()

    def tick(self, now: float) -> None:
        self.inner.tick(now)

    def close(self) -> None:
        if self.closed:
            return
        super().close()
        self.inner.close()
