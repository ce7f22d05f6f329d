"""Typed metrics registry — the platform's one source of numeric truth.

Three instrument kinds, all label-aware (a labeled instrument is a
family of independent series, one per label combination):

  Counter    monotonically increasing total.  ``inc(n)`` for native
             accounting; ``sync(total)`` adopts an externally-tracked
             monotonic total (used to fold legacy counters — sink
             counters, ``Metrics`` scalars — into the registry without
             double bookkeeping).
  Gauge      point-in-time value (``set``/``add``).
  Histogram  fixed log-spaced buckets (base^i ladder) with O(1)
             ``observe`` and cheap ``quantile(q)`` reads (p50/p99
             resolve to a bucket upper bound — conservative, never
             under-reports).

The registry renders two stable surfaces:

  render_prometheus()  text exposition (``# HELP`` / ``# TYPE`` /
                       ``name{label="v"} value`` + histogram
                       ``_bucket``/``_sum``/``_count`` rows)
  snapshot()           json-safe nested dict (counters / gauges /
                       histograms), the shape dashboards and the
                       self-monitoring connector consume

Collectors: components whose counters live elsewhere (sink stacks, the
store plane) register a zero-arg callback via ``add_collector``; every
``snapshot()``/``render_prometheus()`` call runs the collectors first,
so exposition is always current without per-event sync cost.
"""
from __future__ import annotations

import math
import threading
from typing import Callable, Dict, Iterator, List, Optional, Tuple

_LabelKey = Tuple[Tuple[str, str], ...]


def _label_key(labels: Dict[str, object]) -> _LabelKey:
    return tuple(sorted((str(k), str(v)) for k, v in labels.items()))


def _escape(value: str) -> str:
    return (value.replace("\\", r"\\").replace("\n", r"\n")
            .replace('"', r'\"'))


def _fmt_labels(key: _LabelKey) -> str:
    if not key:
        return ""
    inner = ",".join(f'{k}="{_escape(v)}"' for k, v in key)
    return "{" + inner + "}"


def _fmt_value(v: float) -> str:
    if v == math.inf:
        return "+Inf"
    f = float(v)
    return str(int(f)) if f.is_integer() else repr(f)


class _Instrument:
    kind = "untyped"

    def __init__(self, name: str, help: str = ""):
        self.name = name
        self.help = help
        self._lock = threading.Lock()

    def series(self) -> Dict[_LabelKey, object]:
        raise NotImplementedError

    def items(self) -> List[Tuple[dict, object]]:
        """[(labels_dict, value), ...] in stable label order."""
        with self._lock:
            ser = dict(self.series())
        return [(dict(k), v) for k, v in sorted(ser.items())]


class Counter(_Instrument):
    kind = "counter"

    def __init__(self, name: str, help: str = ""):
        super().__init__(name, help)
        self._values: Dict[_LabelKey, float] = {}

    def series(self) -> Dict[_LabelKey, float]:
        return self._values

    def inc(self, n: float = 1.0, **labels) -> None:
        if n < 0:
            raise ValueError(f"counter {self.name} cannot decrease")
        key = _label_key(labels)
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + n

    def sync(self, total: float, **labels) -> None:
        """Adopt an externally-tracked monotonic total: the series jumps
        to ``max(current, total)`` — safe to call repeatedly from a
        collector without double counting."""
        key = _label_key(labels)
        with self._lock:
            self._values[key] = max(self._values.get(key, 0.0), float(total))

    def value(self, **labels) -> float:
        with self._lock:
            return self._values.get(_label_key(labels), 0.0)

    def total(self) -> float:
        with self._lock:
            return sum(self._values.values())


class Gauge(_Instrument):
    kind = "gauge"

    def __init__(self, name: str, help: str = ""):
        super().__init__(name, help)
        self._values: Dict[_LabelKey, float] = {}

    def series(self) -> Dict[_LabelKey, float]:
        return self._values

    def set(self, v: float, **labels) -> None:
        with self._lock:
            self._values[_label_key(labels)] = float(v)

    def add(self, n: float = 1.0, **labels) -> None:
        key = _label_key(labels)
        with self._lock:
            self._values[key] = self._values.get(key, 0.0) + n

    def value(self, **labels) -> float:
        with self._lock:
            return self._values.get(_label_key(labels), 0.0)


class _HistSeries:
    __slots__ = ("counts", "count", "sum", "min", "max")

    def __init__(self, n_buckets: int):
        self.counts = [0] * n_buckets
        self.count = 0
        self.sum = 0.0
        self.min = math.inf
        self.max = -math.inf


class Histogram(_Instrument):
    """Fixed log-bucket histogram: bucket ``i`` covers values ``<=
    min_bound * base**i`` (cumulative, Prometheus ``le`` semantics); one
    final +Inf bucket catches the tail.  Log spacing keeps relative
    error bounded by ``base`` across ~12 orders of magnitude with a few
    dozen buckets — the right trade for latency distributions."""

    kind = "histogram"

    def __init__(self, name: str, help: str = "", *,
                 min_bound: float = 1e-6, base: float = 2.0,
                 num_buckets: int = 40):
        super().__init__(name, help)
        if min_bound <= 0 or base <= 1 or num_buckets < 1:
            raise ValueError("need min_bound > 0, base > 1, num_buckets >= 1")
        self.bounds = [min_bound * base ** i for i in range(num_buckets)]
        self.bounds.append(math.inf)
        self._series: Dict[_LabelKey, _HistSeries] = {}

    def series(self) -> Dict[_LabelKey, _HistSeries]:
        return self._series

    def _bucket_index(self, v: float) -> int:
        lo, hi = 0, len(self.bounds) - 1
        while lo < hi:
            mid = (lo + hi) // 2
            if v <= self.bounds[mid]:
                hi = mid
            else:
                lo = mid + 1
        return lo

    def observe(self, v: float, **labels) -> None:
        v = float(v)
        key = _label_key(labels)
        idx = self._bucket_index(v)
        with self._lock:
            s = self._series.get(key)
            if s is None:
                s = self._series[key] = _HistSeries(len(self.bounds))
            s.counts[idx] += 1
            s.count += 1
            s.sum += v
            if v < s.min:
                s.min = v
            if v > s.max:
                s.max = v

    def observe_batch(self, values, **labels) -> None:
        """Observe many values under one label set with a single lock
        acquisition — the always-on latency plane's hot path (one call
        per fetch / per delivery write, not per document)."""
        if not values:
            return
        key = _label_key(labels)
        bucket = self._bucket_index
        idxs = [bucket(float(v)) for v in values]
        with self._lock:
            s = self._series.get(key)
            if s is None:
                s = self._series[key] = _HistSeries(len(self.bounds))
            counts = s.counts
            for i in idxs:
                counts[i] += 1
            s.count += len(values)
            for v in values:          # one add per value, as observe() does
                s.sum += float(v)
            lo, hi = min(values), max(values)
            if lo < s.min:
                s.min = float(lo)
            if hi > s.max:
                s.max = float(hi)

    def count(self, **labels) -> int:
        with self._lock:
            s = self._series.get(_label_key(labels))
            return 0 if s is None else s.count

    def sum(self, **labels) -> float:
        with self._lock:
            s = self._series.get(_label_key(labels))
            return 0.0 if s is None else s.sum

    def quantile(self, q: float, **labels) -> float:
        """Value at quantile ``q`` (0..1], resolved to the containing
        bucket's upper bound (the observed max caps the +Inf bucket).
        Returns 0.0 for an empty series."""
        if not 0.0 < q <= 1.0:
            raise ValueError("q must be in (0, 1]")
        with self._lock:
            s = self._series.get(_label_key(labels))
            if s is None or s.count == 0:
                return 0.0
            target = q * s.count
            cum = 0
            for i, c in enumerate(s.counts):
                cum += c
                if cum >= target:
                    bound = self.bounds[i]
                    return s.max if bound == math.inf else min(bound, s.max)
            return s.max

    def summary(self, **labels) -> dict:
        """count / sum / min / max / p50 / p99 in one locked read."""
        p50 = self.quantile(0.5, **labels)
        p99 = self.quantile(0.99, **labels)
        with self._lock:
            s = self._series.get(_label_key(labels))
            if s is None or s.count == 0:
                return {"count": 0, "sum": 0.0, "min": 0.0, "max": 0.0,
                        "p50": 0.0, "p99": 0.0}
            return {"count": s.count, "sum": s.sum, "min": s.min,
                    "max": s.max, "p50": p50, "p99": p99}


class MetricsRegistry:
    """Name -> instrument map with get-or-create accessors, pluggable
    collectors, Prometheus text exposition, and a json-safe snapshot."""

    def __init__(self):
        self._lock = threading.Lock()
        self._instruments: Dict[str, _Instrument] = {}
        self._collectors: List[Callable[[], None]] = []

    # ---- instrument accessors (get-or-create) -----------------------------
    def _get_or_create(self, cls, name: str, help: str, **kwargs):
        with self._lock:
            inst = self._instruments.get(name)
            if inst is None:
                inst = cls(name, help, **kwargs)
                self._instruments[name] = inst
            elif not isinstance(inst, cls):
                raise ValueError(
                    f"metric {name!r} already registered as {inst.kind}")
            return inst

    def counter(self, name: str, help: str = "") -> Counter:
        return self._get_or_create(Counter, name, help)

    def gauge(self, name: str, help: str = "") -> Gauge:
        return self._get_or_create(Gauge, name, help)

    def histogram(self, name: str, help: str = "", **kwargs) -> Histogram:
        return self._get_or_create(Histogram, name, help, **kwargs)

    def get(self, name: str) -> Optional[_Instrument]:
        with self._lock:
            return self._instruments.get(name)

    def names(self) -> Tuple[str, ...]:
        with self._lock:
            return tuple(sorted(self._instruments))

    def __contains__(self, name: str) -> bool:
        with self._lock:
            return name in self._instruments

    # ---- collectors --------------------------------------------------------
    def add_collector(self, fn: Callable[[], None]) -> None:
        """Register a zero-arg callback that refreshes externally-owned
        series (via ``Counter.sync`` / ``Gauge.set``); runs before every
        snapshot/exposition."""
        with self._lock:
            self._collectors.append(fn)

    def collect(self) -> None:
        with self._lock:
            collectors = list(self._collectors)
        for fn in collectors:
            fn()

    # ---- surfaces ----------------------------------------------------------
    def _sorted_instruments(self) -> List[_Instrument]:
        with self._lock:
            return [self._instruments[n] for n in sorted(self._instruments)]

    def snapshot(self) -> dict:
        """json-safe dump: ``{"counters": {name: {"help", "series":
        [{"labels", "value"}]}}, "gauges": {...}, "histograms": {name:
        {"help", "series": [{"labels", "count", "sum", "min", "max",
        "p50", "p99"}]}}}``."""
        self.collect()
        out: dict = {"counters": {}, "gauges": {}, "histograms": {}}
        for inst in self._sorted_instruments():
            if isinstance(inst, Histogram):
                series = [{"labels": labels, **inst.summary(**labels)}
                          for labels, _ in inst.items()]
                out["histograms"][inst.name] = {"help": inst.help,
                                                "series": series}
            elif isinstance(inst, (Counter, Gauge)):
                series = [{"labels": labels, "value": float(v)}
                          for labels, v in inst.items()]
                group = "counters" if inst.kind == "counter" else "gauges"
                out[group][inst.name] = {"help": inst.help, "series": series}
        return out

    def render_prometheus(self) -> str:
        """Prometheus text exposition format (version 0.0.4)."""
        self.collect()
        lines: List[str] = []
        for inst in self._sorted_instruments():
            if inst.help:
                lines.append(f"# HELP {inst.name} {_escape(inst.help)}")
            lines.append(f"# TYPE {inst.name} {inst.kind}")
            if isinstance(inst, Histogram):
                for labels, _ in inst.items():
                    key = _label_key(labels)
                    with inst._lock:
                        s = inst._series.get(key)
                        counts = list(s.counts) if s else []
                        total, vsum = (s.count, s.sum) if s else (0, 0.0)
                    cum = 0
                    for bound, c in zip(inst.bounds, counts):
                        cum += c
                        le = _fmt_labels(key + (("le", _fmt_value(bound)),))
                        lines.append(f"{inst.name}_bucket{le} {cum}")
                    lbl = _fmt_labels(key)
                    lines.append(f"{inst.name}_sum{lbl} {_fmt_value(vsum)}")
                    lines.append(f"{inst.name}_count{lbl} {total}")
            else:
                for labels, v in inst.items():
                    lbl = _fmt_labels(_label_key(labels))
                    lines.append(f"{inst.name}{lbl} {_fmt_value(float(v))}")
        return "\n".join(lines) + "\n"
