"""Typed, thread-safe metrics registry — the one place observations go.

Before this module the repo had three look-alike stat sinks
(``resilience/counters.py``, ``serving/metrics.py``,
``compression/stats.py``), each a private dict with its own Tracer
mirroring and no way to read them all at once: a running cluster had no
live stats surface, only post-mortem trace dumps.  This registry is the
shared substrate they now delegate to:

  * :class:`Counter` — monotonic; ``inc()`` is the hot-path op (one
    lock, one add; Tracer mirroring only when tracing is enabled).
  * :class:`Gauge` — last-written value (window occupancy, queue depth,
    credit levels).  Unlike the old ``ServeMetrics.gauge`` (which only
    emitted a trace event), gauges are *stored*, so a live scrape sees
    them.
  * :class:`Histogram` — fixed exposition buckets plus a bounded
    reservoir of recent raw samples for percentile queries (TTFT/TPOT
    p50/p99 come from here).

Every metric keeps the pre-registry Tracer behavior: when
``BYTEPS_TRACE_PATH`` is set, a counter bump lands on the chrome-trace
timeline as the same instant + counter-track pair the resilience/serving
subsystems always emitted, so existing traces look identical.

Exposition: :meth:`MetricsRegistry.snapshot` (plain dicts, used by
``OP_STATS`` and the serving TCP STATS reply), :meth:`to_json`, and
:meth:`to_prometheus` (text format 0.0.4, served by
``observability/scrape.py`` under ``BYTEPS_METRICS_PORT``).

One process-global registry (``get_registry()``) backs the per-process
scrape endpoints; isolated ``MetricsRegistry()`` instances exist so
tests can count in a vacuum (the pattern the old per-class
instances supported).
"""

from __future__ import annotations

import json
import threading
from typing import Dict, List, Optional, Tuple

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "get_registry", "reset_registry",
]


def _get_process_tracer():
    from ..common.tracing import get_tracer

    return get_tracer()


class _Metric:
    """Shared plumbing: identity, static labels, Tracer mirroring."""

    __slots__ = ("name", "track", "labels", "label_key", "_lock", "_tracer")

    def __init__(self, name: str, track: str, labels: Dict[str, str],
                 tracer=None):
        self.name = name
        self.track = track
        self.labels = labels
        # cached: the snapshot key AND the mirrored Tracer series name —
        # labeled metrics (per-shard gauges) must land on distinct
        # counter tracks, or Perfetto conflates every shard's values
        # into one sawtooth under the bare name
        if labels:
            inner = ",".join(f"{k}={v}" for k, v in sorted(labels.items()))
            self.label_key = f"{name}{{{inner}}}"
        else:
            self.label_key = name
        self._lock = threading.Lock()
        self._tracer = tracer

    def _get_tracer(self):
        # None = the process tracer, resolved per call so a
        # reset_tracer() mid-run is honored (the pre-registry classes
        # behaved this way too)
        return self._tracer if self._tracer is not None \
            else _get_process_tracer()


class Counter(_Metric):
    """Monotonic counter.  ``instants=False`` drops the per-bump instant
    event (bytes/frame counters would otherwise flood the trace) while
    keeping the counter value track; ``mirror=False`` drops Tracer
    mirroring entirely — registry-only metrics for per-frame hot paths
    whose trace-level detail already comes from spans (the wire
    engine's counters; docs/observability.md "Overhead").  ``inc`` adds
    whatever it is given: a cumulative-seconds counter
    (``serve.tick_seconds``) adds floats."""

    __slots__ = ("_value", "_instants", "_mirror")

    def __init__(self, name: str, track: str, labels: Dict[str, str],
                 tracer=None, instants: bool = True, mirror: bool = True):
        super().__init__(name, track, labels, tracer)
        self._value = 0
        self._instants = instants
        self._mirror = mirror

    def inc(self, n: int = 1, **args) -> int:
        with self._lock:
            self._value += n
            total = self._value
        if self._mirror:
            tracer = self._get_tracer()
            if tracer.enabled:
                if self._instants:
                    # "name" would collide with instant()'s own first param
                    safe = {("tensor" if k == "name" else k): v
                            for k, v in args.items()}
                    tracer.instant(self.label_key, self.track, **safe)
                tracer.counter(self.label_key, total, self.track)
        return total

    @property
    def value(self) -> int:
        with self._lock:
            return self._value


class Gauge(_Metric):
    """Last-written value; ``set`` mirrors onto the Tracer value track
    (``mirror=False`` = registry-only, as on :class:`Counter`)."""

    __slots__ = ("_value", "_mirror")

    def __init__(self, name: str, track: str, labels: Dict[str, str],
                 tracer=None, mirror: bool = True):
        super().__init__(name, track, labels, tracer)
        self._value = 0.0
        self._mirror = mirror

    def set(self, value: float) -> None:
        with self._lock:
            self._value = float(value)
        if self._mirror:
            tracer = self._get_tracer()
            if tracer.enabled:
                tracer.counter(self.label_key, value, self.track)

    def inc(self, n: float = 1.0) -> float:
        with self._lock:
            self._value += n
            v = self._value
        if self._mirror:
            tracer = self._get_tracer()
            if tracer.enabled:
                tracer.counter(self.label_key, v, self.track)
        return v

    def dec(self, n: float = 1.0) -> float:
        return self.inc(-n)

    @property
    def value(self) -> float:
        with self._lock:
            return self._value


# default exposition buckets: latency-shaped (seconds), wide enough for
# queue waits and narrow enough for decode ticks
_DEFAULT_BUCKETS = (0.001, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                    1.0, 2.5, 5.0, 10.0)


def _nearest_rank(vals: List[float], q: float) -> float:
    """Nearest-rank percentile of pre-sorted ``vals`` — the ONE rank
    formula behind both ``percentile()`` and ``state()``, so
    /metrics.json and ``summary()`` can never disagree on p50/p99."""
    if not vals:
        return 0.0
    k = max(0, min(len(vals) - 1, int(round(q / 100.0 * (len(vals) - 1)))))
    return vals[k]


class Histogram(_Metric):
    """Cumulative-bucket histogram + bounded sample reservoir.

    Buckets give the Prometheus exposition; the reservoir (a ring of the
    most recent ``max_samples`` raw observations) gives exact-ish
    percentiles for ``summary()``-style reporting without unbounded
    memory — the fix for the old ``ServeMetrics`` lists that grew one
    float per request forever.
    """

    __slots__ = ("buckets", "_counts", "_count", "_sum", "_samples",
                 "_max_samples", "_next")

    def __init__(self, name: str, track: str, labels: Dict[str, str],
                 tracer=None, buckets: Optional[Tuple[float, ...]] = None,
                 max_samples: int = 4096):
        super().__init__(name, track, labels, tracer)
        self.buckets = tuple(sorted(buckets or _DEFAULT_BUCKETS))
        self._counts = [0] * (len(self.buckets) + 1)  # +inf bucket
        self._count = 0
        self._sum = 0.0
        self._samples: List[float] = []
        self._max_samples = max(1, int(max_samples))
        self._next = 0  # ring cursor once the reservoir is full

    def observe(self, value: float) -> None:
        value = float(value)
        with self._lock:
            self._count += 1
            self._sum += value
            i = 0
            for i, b in enumerate(self.buckets):
                if value <= b:
                    break
            else:
                i = len(self.buckets)
            self._counts[i] += 1
            if len(self._samples) < self._max_samples:
                self._samples.append(value)
            else:
                self._samples[self._next] = value
                self._next = (self._next + 1) % self._max_samples

    def percentile(self, q: float) -> float:
        """Nearest-rank percentile over the sample reservoir (recent
        ``max_samples`` observations)."""
        with self._lock:
            vals = sorted(self._samples)
        return _nearest_rank(vals, q)

    @property
    def count(self) -> int:
        with self._lock:
            return self._count

    @property
    def sum(self) -> float:
        with self._lock:
            return self._sum

    def state(self) -> Dict[str, object]:
        """Snapshot dict: count/sum/percentiles + cumulative buckets."""
        with self._lock:
            counts = list(self._counts)
            count, total = self._count, self._sum
            vals = sorted(self._samples)
        cum, acc = [], 0
        for c in counts[:-1]:
            acc += c
            cum.append(acc)
        return {"count": count, "sum": total,
                "p50": _nearest_rank(vals, 50),
                "p90": _nearest_rank(vals, 90),
                "p99": _nearest_rank(vals, 99),
                "buckets": {str(b): c
                            for b, c in zip(self.buckets, cum)}}


def _default_track(name: str) -> str:
    """Chrome-trace row for a metric: its namespace prefix
    (``resilience.retry`` -> row ``resilience``) — exactly the stage the
    pre-registry classes hardcoded."""
    return name.split(".", 1)[0] if "." in name else "metrics"


class MetricsRegistry:
    """Get-or-create metric store.  A name+labels pair maps to exactly
    one metric; re-requesting it with a different type raises (typed
    registry — silent type morphing is how dashboards lie)."""

    def __init__(self, tracer=None):
        self._metrics: Dict[Tuple[str, frozenset], _Metric] = {}
        self._lock = threading.Lock()
        self._tracer = tracer

    # ------------------------------------------------------------ factories

    def _get_or_create(self, cls, name: str, track: Optional[str],
                       labels: Dict[str, str], **kw) -> _Metric:
        key = (name, frozenset(labels.items()))
        with self._lock:
            m = self._metrics.get(key)
            if m is None:
                m = cls(name, track or _default_track(name), labels,
                        tracer=self._tracer, **kw)
                self._metrics[key] = m
            elif not isinstance(m, cls):
                raise TypeError(
                    f"metric {name!r} already registered as "
                    f"{type(m).__name__}, requested {cls.__name__}")
            return m

    def counter(self, name: str, track: Optional[str] = None,
                instants: bool = True, mirror: bool = True,
                **labels) -> Counter:
        return self._get_or_create(Counter, name, track,
                                   {k: str(v) for k, v in labels.items()},
                                   instants=instants, mirror=mirror)

    def gauge(self, name: str, track: Optional[str] = None,
              mirror: bool = True, **labels) -> Gauge:
        return self._get_or_create(Gauge, name, track,
                                   {k: str(v) for k, v in labels.items()},
                                   mirror=mirror)

    def histogram(self, name: str, track: Optional[str] = None,
                  buckets: Optional[Tuple[float, ...]] = None,
                  max_samples: int = 4096, **labels) -> Histogram:
        return self._get_or_create(Histogram, name, track,
                                   {k: str(v) for k, v in labels.items()},
                                   buckets=buckets, max_samples=max_samples)

    def get(self, name: str, **labels) -> Optional[_Metric]:
        key = (name, frozenset((k, str(v)) for k, v in labels.items()))
        with self._lock:
            return self._metrics.get(key)

    def remove(self, name: str, **labels) -> bool:
        """Drop one metric.  The next get-or-create for the same
        name+labels starts from zero — how the subsystem ``reset_*``
        helpers clear counts that outlive their singleton on the shared
        process registry.  Callers still holding the removed object see
        an orphan: it keeps counting but no scrape reports it."""
        key = (name, frozenset((k, str(v)) for k, v in labels.items()))
        with self._lock:
            return self._metrics.pop(key, None) is not None

    def remove_prefix(self, prefix: str) -> int:
        """Drop every metric whose name starts with ``prefix`` (any
        labels); returns how many were removed."""
        with self._lock:
            doomed = [k for k in self._metrics if k[0].startswith(prefix)]
            for k in doomed:
                del self._metrics[k]
        return len(doomed)

    # ----------------------------------------------------------- exposition

    def _metrics_snapshot(self) -> List[_Metric]:
        with self._lock:
            return list(self._metrics.values())

    def snapshot(self) -> Dict[str, Dict[str, object]]:
        """Point-in-time copy — plain dicts, isolated from later
        mutation: ``{"counters": {...}, "gauges": {...},
        "histograms": {...}}`` keyed by ``name{label=value}``."""
        out: Dict[str, Dict[str, object]] = {
            "counters": {}, "gauges": {}, "histograms": {}}
        for m in self._metrics_snapshot():
            if isinstance(m, Counter):
                out["counters"][m.label_key] = m.value
            elif isinstance(m, Gauge):
                out["gauges"][m.label_key] = m.value
            elif isinstance(m, Histogram):
                out["histograms"][m.label_key] = m.state()
        return out

    def to_json(self) -> str:
        return json.dumps(self.snapshot(), sort_keys=True)

    def to_prometheus(self) -> str:
        """Prometheus text exposition (format 0.0.4).  Metric names are
        sanitized (``.`` -> ``_``) and prefixed ``byteps_``; counters
        get the conventional ``_total`` suffix."""
        lines: List[str] = []
        seen_types: Dict[str, str] = {}

        def base(name: str, suffix: str = "") -> str:
            safe = "".join(c if (c.isalnum() or c == "_") else "_"
                           for c in name)
            return f"byteps_{safe}{suffix}"

        def fmt_labels(labels: Dict[str, str], extra=()) -> str:
            items = sorted(labels.items()) + list(extra)
            if not items:
                return ""
            return "{" + ",".join(f'{k}="{v}"' for k, v in items) + "}"

        def typeline(name: str, kind: str):
            if seen_types.get(name) != kind:
                seen_types[name] = kind
                lines.append(f"# TYPE {name} {kind}")

        for m in sorted(self._metrics_snapshot(), key=lambda x: x.name):
            if isinstance(m, Counter):
                n = base(m.name, "_total")
                typeline(n, "counter")
                lines.append(f"{n}{fmt_labels(m.labels)} {m.value}")
            elif isinstance(m, Gauge):
                n = base(m.name)
                typeline(n, "gauge")
                lines.append(f"{n}{fmt_labels(m.labels)} {m.value:g}")
            elif isinstance(m, Histogram):
                n = base(m.name)
                typeline(n, "histogram")
                st = m.state()
                for b, c in st["buckets"].items():
                    lines.append(
                        f"{n}_bucket"
                        f"{fmt_labels(m.labels, [('le', b)])} {c}")
                lines.append(
                    f"{n}_bucket"
                    f"{fmt_labels(m.labels, [('le', '+Inf')])}"
                    f" {st['count']}")
                lines.append(f"{n}_sum{fmt_labels(m.labels)}"
                             f" {st['sum']:g}")
                lines.append(f"{n}_count{fmt_labels(m.labels)}"
                             f" {st['count']}")
        return "\n".join(lines) + ("\n" if lines else "")


_registry: Optional[MetricsRegistry] = None
_registry_lock = threading.Lock()


def get_registry() -> MetricsRegistry:
    """The process-global registry — what ``/metrics``, ``OP_STATS`` and
    the serving STATS reply expose."""
    global _registry
    with _registry_lock:
        if _registry is None:
            _registry = MetricsRegistry()
        return _registry


def reset_registry() -> None:
    global _registry
    with _registry_lock:
        _registry = None
