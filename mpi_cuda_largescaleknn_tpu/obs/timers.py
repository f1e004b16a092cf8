"""Spans, counters and latency histograms.

The reference never measures itself (SURVEY.md §5: no timers anywhere, stdout
progress lines only) — this subsystem is the capability the TPU build adds so
BASELINE numbers can be produced at all. A span (``PhaseTimers.phase``) adds
its wall-clock to a per-name aggregate and, while a ``jax.profiler`` trace
is being collected, writes a host event on the profiler's clock, so a
trace's idle gaps name the host work that left the device waiting. Spans
and counts made while a trace is collected are also summed process-wide
(``traced_spans_snapshot``, ``traced_counters_snapshot``). Log-bucketed
latency histograms (p50/p95/p99) are shared by ``--timings``, the serving
layer's ``/metrics`` endpoint, and ``tools/loadgen.py``.

JAX is never imported here: a span looks for ``jax.profiler`` only once the
process has imported JAX itself (a process without JAX runs no profiler),
so the JAX-free load generator imports this module too.
"""

from __future__ import annotations

import bisect
import contextlib
import contextvars
import json
import math
import sys
import threading
import time
from dataclasses import dataclass, field


# shared histogram geometry — module-level so every histogram (server,
# loadgen, timers) is mergeable and renders identical /metrics buckets:
# geometric buckets, ~12% relative resolution, spanning [1 us, 120 s]
_HIST_FACTOR = 2 ** 0.1665
_HIST_BOUNDS: list[float] = [
    1e-6 * _HIST_FACTOR ** i
    for i in range(int(math.log(120.0 / 1e-6, _HIST_FACTOR)) + 2)
]


class LatencyHistogram:
    """Log-bucketed latency histogram with bounded memory.

    Buckets are geometric (``_HIST_BOUNDS``: factor ~1.122, ~12% relative
    resolution, [1 us, 120 s]); an observation beyond the top bound lands in
    a +inf overflow bucket. Percentiles are read off the cumulative counts and
    reported as the matched bucket's upper bound, so a quantile is
    conservative by at most one bucket width. ``record`` is thread-safe
    (serving handler threads and the loadgen's workers all feed one
    histogram).
    """

    _BOUNDS = _HIST_BOUNDS

    def __init__(self):
        self._lock = threading.Lock()
        self.counts = [0] * (len(self._BOUNDS) + 1)
        self.count = 0
        self.sum_seconds = 0.0

    def record(self, seconds: float) -> None:
        b = bisect.bisect_left(self._BOUNDS, seconds)
        with self._lock:
            self.counts[b] += 1
            self.count += 1
            self.sum_seconds += seconds

    def percentile(self, p: float) -> float:
        """Latency (seconds) at quantile ``p`` in [0, 100]; nan when empty."""
        if self.count == 0:
            return float("nan")
        rank = max(1, math.ceil(self.count * p / 100.0))
        seen = 0
        for i, c in enumerate(self.counts):
            seen += c
            if seen >= rank:
                return (self._BOUNDS[i] if i < len(self._BOUNDS)
                        else float("inf"))
        return float("inf")

    def merge(self, other: "LatencyHistogram") -> None:
        with self._lock:
            for i, c in enumerate(other.counts):
                self.counts[i] += c
            self.count += other.count
            self.sum_seconds += other.sum_seconds

    def report(self) -> dict:
        def q(p: float):
            # None, not nan/inf: the report is a strict-JSON artifact
            # (loadgen --out joins the BENCH series; /stats is scraped) and
            # json.dumps would emit the non-standard NaN/Infinity tokens
            v = self.percentile(p)
            return round(v, 6) if math.isfinite(v) else None

        return {"count": self.count,
                "sum_seconds": round(self.sum_seconds, 6),
                "p50": q(50), "p95": q(95), "p99": q(99)}

    def prometheus_lines(self, name: str) -> list[str]:
        """Render as a Prometheus-text histogram (cumulative ``le`` buckets).

        Empty buckets are elided (the geometry has ~170 buckets; a scrape
        only needs the populated prefix sums plus the +Inf terminal)."""
        lines = [f"# TYPE {name} histogram"]
        cum = 0
        for i, c in enumerate(self.counts[:-1]):
            cum += c
            if c:
                lines.append(
                    f'{name}_bucket{{le="{self._BOUNDS[i]:.6g}"}} {cum}')
        lines.append(f'{name}_bucket{{le="+Inf"}} {self.count}')
        lines.append(f"{name}_sum {self.sum_seconds:.6g}")
        lines.append(f"{name}_count {self.count}")
        return lines


def labeled_metric_lines(name: str, rows, kind: str = "counter"):
    """Render one Prometheus text-format metric family with labels:
    ``rows`` is an iterable of ``(labels_dict, value)`` pairs, emitted in
    the caller's order (callers iterate sorted snapshots, so scrapes are
    deterministic). Shared by the wire-codec traffic stats and any other
    multi-labeled family — one place owns the label quoting."""
    rows = list(rows)
    if not rows:
        return []
    lines = [f"# TYPE {name} {kind}"]
    for labels, value in rows:
        lab = ",".join(f'{k}="{v}"' for k, v in labels.items())
        lines.append(f"{name}{{{lab}}} {value}")
    return lines


#: tags that every span opened in the current context carries as profiler
#: metadata (``span_tags``): the serving batcher tags its engine calls
#: with the batch id this way, since the engine never sees the batcher
_TAGS: contextvars.ContextVar[dict] = contextvars.ContextVar(
    "span_tags", default={})


@contextlib.contextmanager
def span_tags(**tags):
    """Add ``tags`` (e.g. ``batch=7``) to every span opened inside the
    block on this thread. Another thread does not inherit them: a caller
    that hands work to a pool passes ``current_span_tags()`` along."""
    token = _TAGS.set({**_TAGS.get(), **tags})
    try:
        yield
    finally:
        _TAGS.reset(token)


def current_span_tags() -> dict:
    return _TAGS.get()


_annotation = None


def _trace_annotation():
    """``jax.profiler.TraceAnnotation``, or None while the process has not
    imported JAX (then no profiler can be running)."""
    global _annotation
    if _annotation is None and sys.modules.get("jax") is not None:
        from jax.profiler import TraceAnnotation

        _annotation = TraceAnnotation
    return _annotation


# spans that closed, and counts that were added, while a profiler was
# collecting, over the whole process: {trace name: [count, seconds]} and
# {counter: total}
_traced_lock = threading.Lock()
_traced: dict[str, list] = {}
_traced_counts: dict[str, int] = {}


def _tracing() -> bool:
    ann = _trace_annotation()
    return ann is not None and ann.is_enabled()


def _note_traced(name: str, seconds: float) -> None:
    if _tracing():
        with _traced_lock:
            agg = _traced.setdefault(name, [0, 0.0])
            agg[0] += 1
            agg[1] += seconds


def _note_traced_count(name: str, by: int) -> None:
    if _tracing():
        with _traced_lock:
            _traced_counts[name] = _traced_counts.get(name, 0) + by


def traced_spans_snapshot() -> dict:
    """``{name: (count, seconds)}`` of every span of this process that
    closed while a ``jax.profiler`` trace was being collected, under the
    name the trace shows: the spans of the traced slices, summed without
    reading the trace back."""
    with _traced_lock:
        return {n: (c, s) for n, (c, s) in _traced.items()}


def traced_counters_snapshot() -> dict:
    """``{counter: total}`` of every ``PhaseTimers.count`` of this process
    made while a ``jax.profiler`` trace was being collected: the counts of
    the traced slices, summed over every ``PhaseTimers``, as
    ``traced_spans_snapshot`` sums spans."""
    with _traced_lock:
        return dict(_traced_counts)


class _Span:
    """One ``PhaseTimers.phase`` block: a ``perf_counter`` pair and an
    aggregate update, plus a profiler event only while a trace is being
    collected (an annotation is not even built otherwise)."""

    __slots__ = ("timers", "name", "span", "meta", "ann", "t0")

    def __init__(self, timers: "PhaseTimers", name: str, span: str | None,
                 meta: dict):
        self.timers, self.name, self.span, self.meta = timers, name, span, meta

    def __enter__(self) -> "_Span":
        ann = _trace_annotation()
        self.ann = None
        if ann is not None and ann.is_enabled():
            self.ann = ann(self.span or self.name, **{**_TAGS.get(),
                                                      **self.meta})
            self.ann.__enter__()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.timers.record(self.name, time.perf_counter() - self.t0,
                           span=self.span)
        if self.ann is not None:
            self.ann.__exit__(*exc)


@dataclass
class PhaseRecord:
    seconds: float = 0.0
    calls: int = 0


@dataclass
class PhaseTimers:
    phases: dict[str, PhaseRecord] = field(default_factory=dict)
    histograms: dict[str, LatencyHistogram] = field(default_factory=dict)
    gauges: dict[str, float] = field(default_factory=dict)
    counters: dict[str, int] = field(default_factory=dict)
    _counter_lock: threading.Lock = field(default_factory=threading.Lock,
                                          repr=False)
    _phase_lock: threading.Lock = field(default_factory=threading.Lock,
                                        repr=False)

    def phase(self, name: str, span: str | None = None, **meta) -> "_Span":
        """Time the block as phase ``name`` (``phases[name]``: calls and
        seconds). While a ``jax.profiler`` trace is collected the block is
        also a host event named ``span`` (default ``name``) whose metadata
        is ``meta`` plus the context's ``span_tags``, e.g. ``req=12``."""
        return _Span(self, name, span, meta)

    def record(self, name: str, seconds: float,
               span: str | None = None) -> None:
        """Add one span of ``seconds`` to ``name``'s aggregate without a
        profiler event: for a wait that begins on one thread and ends on
        another (a profiler event opens and closes on one thread)."""
        with self._phase_lock:
            rec = self.phases.get(name)
            if rec is None:
                rec = self.phases[name] = PhaseRecord()
            rec.seconds += seconds
            rec.calls += 1
        _note_traced(span or name, seconds)

    def spans_snapshot(self) -> dict:
        """``{name: (count, seconds)}`` of every phase, for before/after
        deltas across a window (as ``counters_snapshot`` for counters)."""
        with self._phase_lock:
            return {n: (r.calls, r.seconds) for n, r in self.phases.items()}

    def hist(self, name: str) -> LatencyHistogram:
        """Named latency histogram (created on first use); shows up in
        ``report()`` next to the phases, so ``--timings`` callers and the
        serving ``/stats`` endpoint share one percentile source."""
        h = self.histograms.get(name)
        if h is None:
            h = self.histograms[name] = LatencyHistogram()
        return h

    def gauge(self, name: str, value: float) -> None:
        """Point-in-time gauge (e.g. pipeline occupancy). Single dict store,
        so concurrent writers are last-writer-wins — exactly gauge
        semantics; no lock needed."""
        self.gauges[name] = float(value)

    def count(self, name: str, by: int = 1) -> None:
        """Monotonic counter (e.g. ``fetch_bytes``, ``result_rows``) —
        unlike gauges, increments from concurrent completion threads must
        not lose updates, hence the lock. While a profiler trace is being
        collected the count also adds to ``traced_counters_snapshot()``."""
        with self._counter_lock:
            self.counters[name] = self.counters.get(name, 0) + int(by)
        _note_traced_count(name, int(by))

    def counter(self, name: str) -> int:
        with self._counter_lock:
            return self.counters.get(name, 0)

    def counters_snapshot(self) -> dict:
        """Atomic copy of every counter — for before/after deltas across a
        measurement window (e.g. perfbench/systems/serve.py diffing
        ``result_rows`` over the timed window) without racing concurrent
        completion-thread increments between two ``counter()`` reads."""
        with self._counter_lock:
            return dict(self.counters)

    def report(self) -> dict:
        # list() snapshots: a serving /stats scrape may race a worker thread
        # inserting a new phase or histogram mid-iteration
        out = {name: {"seconds": round(s, 6), "calls": c}
               for name, (c, s) in self.spans_snapshot().items()}
        for name, h in list(self.histograms.items()):
            out[name] = h.report()
        for name, v in list(self.gauges.items()):
            out[name] = v
        with self._counter_lock:
            out.update(self.counters)
        return out

    def dump(self) -> str:
        return json.dumps(self.report())
