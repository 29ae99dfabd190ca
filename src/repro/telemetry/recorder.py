"""Recorders: hierarchical spans, a metrics registry, and the null default.

Two implementations of one implicit protocol:

* :class:`NullRecorder` (the module singleton :data:`NULL_RECORDER`) — the
  default every instrumented code path receives when telemetry is off.
  Every method is a constant no-op and ``span`` returns one shared,
  stateless context manager, so hot paths stay allocation-free; callers
  guard tag-building work behind ``recorder.enabled``.
* :class:`Recorder` — the live implementation. Spans nest (a span's
  ``path`` is the slash-joined stack of open span names) and carry wall
  time plus the bucket-solver compile-count delta observed while they
  were open; counters accumulate, gauges keep the last value, histograms
  keep observations, and ``point`` records (round, value) timeline
  samples. Every event lands in the in-memory list and, when the spec
  names a ``jsonl`` path, in the append-only JSONL sink.

While any real span is open the recorder is also *active* for trace-time
kernel tags: :func:`record_kernel_trace`, called from the kernel dispatch
layer (``repro.kernels.cl.ops``) during jit tracing, lands kernel-kind and
shape events on the innermost active recorder. With no active recorder the
hook is a single falsy list check.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from .sinks import JsonlSink
from .spec import TelemetrySpec

__all__ = ["NullRecorder", "NULL_RECORDER", "Recorder", "TelemetrySnapshot",
           "make_recorder", "record_kernel_trace"]


class _NullSpan:
    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()


class NullRecorder:
    """The zero-overhead default: every method is a no-op."""

    __slots__ = ()
    enabled = False

    def span(self, name, **tags):
        return _NULL_SPAN

    def inc(self, name, value=1, **tags):
        pass

    def gauge(self, name, value, **tags):
        pass

    def observe(self, name, value, **tags):
        pass

    def event(self, name, **tags):
        pass

    def point(self, metric, rnd, value):
        pass

    def mark(self) -> int:
        return 0

    def snapshot(self, since: int = 0):
        return None

    def flush(self):
        pass


NULL_RECORDER = NullRecorder()

#: stack of recorders with an open span — the trace-time kernel-tag target
_ACTIVE: List["Recorder"] = []


def record_kernel_trace(name: str, **tags) -> None:
    """Tag the innermost active recorder with a trace-time kernel event.

    Called from the kernel dispatch layer while jit traces a compiled
    region; with telemetry off (no active recorder) this is one list
    check.
    """
    if _ACTIVE:
        _ACTIVE[-1].event(name, **tags)


def _bucket_compiles() -> int:
    # late import: core.batched itself imports this module for NULL_RECORDER
    try:
        from ..core.batched import bucket_compile_count, prox_compile_count
        fit, prox = bucket_compile_count(), prox_compile_count()
        if fit < 0 or prox < 0:
            return -1
        return fit + prox
    except Exception:
        return -1


class _Span:
    """One open span; records start/end events and restores the stack."""

    __slots__ = ("rec", "name", "_t0", "_c0")

    def __init__(self, rec: "Recorder", name: str, tags: dict):
        self.rec = rec
        self.name = name
        if not rec._stack and rec.spec.profile_dir is not None:
            rec._profile_start()          # raises before any state changes
        rec._stack.append(name)
        _ACTIVE.append(rec)
        self._c0 = _bucket_compiles()
        rec._emit("span_start", "/".join(rec._stack), tags=tags or None)
        self._t0 = time.perf_counter()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        dur = time.perf_counter() - self._t0
        c1 = _bucket_compiles()
        rec = self.rec
        path = "/".join(rec._stack)
        rec._emit("span_end", path, value=dur,
                  new_compiles=(c1 - self._c0
                                if c1 >= 0 and self._c0 >= 0 else 0))
        rec._stack.pop()
        _ACTIVE.pop()
        if not rec._stack:
            rec._profile_stop()
        return False


class Recorder:
    """Live telemetry recorder (see module docstring)."""

    enabled = True

    def __init__(self, spec: Optional[TelemetrySpec] = None) -> None:
        self.spec = spec if spec is not None else TelemetrySpec()
        self.events: List[dict] = []
        self._seq = 0
        self._t0 = time.perf_counter()
        self._stack: List[str] = []
        self._sink = (JsonlSink(self.spec.jsonl)
                      if self.spec.jsonl else None)
        self._profiling = False

    # ------------------------------------------------------------ emission
    def _emit(self, kind: str, name: str, value=None, tags=None,
              rnd=None, new_compiles=None) -> None:
        ev = {"seq": self._seq, "t": time.perf_counter() - self._t0,
              "kind": kind, "name": name}
        if value is not None:
            ev["value"] = value
        if rnd is not None:
            ev["round"] = int(rnd)
        if new_compiles is not None:
            ev["new_compiles"] = int(new_compiles)
        if tags:
            ev["tags"] = tags
        self._seq += 1
        self.events.append(ev)
        if self._sink is not None:
            self._sink.write(ev)

    # ------------------------------------------------------------- recording
    def span(self, name: str, **tags) -> _Span:
        """Open a hierarchical span (a context manager); on exit records
        wall seconds and the bucket-solver compile-count delta."""
        if not self.spec.spans:
            return _NULL_SPAN
        return _Span(self, name, tags)

    def inc(self, name: str, value=1, **tags) -> None:
        if self.spec.metrics:
            self._emit("counter", name, value=value, tags=tags or None)

    def gauge(self, name: str, value, **tags) -> None:
        if self.spec.metrics:
            self._emit("gauge", name, value=value, tags=tags or None)

    def observe(self, name: str, value, **tags) -> None:
        if self.spec.metrics:
            self._emit("hist", name, value=value, tags=tags or None)

    def event(self, name: str, **tags) -> None:
        self._emit("event", name, tags=tags or None)

    def point(self, metric: str, rnd: int, value) -> None:
        """One any-time timeline sample: metric value at stream round."""
        if self.spec.metrics:
            self._emit("point", metric, value=float(value), rnd=rnd)

    # ------------------------------------------------------------ profiling
    def _profile_start(self) -> None:
        """Start the ``jax.profiler`` trace for an outermost span. A trace
        that cannot start raises: a run that asked for a profile must not
        silently produce none."""
        import jax
        jax.profiler.start_trace(self.spec.profile_dir)
        self._profiling = True

    def _profile_stop(self) -> None:
        if self._profiling:
            try:
                import jax
                jax.profiler.stop_trace()
            except Exception:
                pass
            self._profiling = False

    # ----------------------------------------------------------- reading out
    def mark(self) -> int:
        """Current event index — pass to :meth:`snapshot` to scope one
        verb's events out of a long-lived recorder."""
        return len(self.events)

    def snapshot(self, since: int = 0) -> "TelemetrySnapshot":
        """Aggregate events[since:] into a :class:`TelemetrySnapshot`."""
        return TelemetrySnapshot.from_events(self.events[since:])

    def flush(self) -> None:
        if self._sink is not None:
            self._sink.flush()

    def close(self) -> None:
        if self._sink is not None:
            self._sink.close()


@dataclasses.dataclass
class TelemetrySnapshot:
    """The in-memory aggregate of one run's events.

    events     — the raw event dicts (same schema as the JSONL log).
    counters   — name -> accumulated total.
    gauges     — name -> last recorded value.
    histograms — name -> list of observations.
    spans      — span path -> {"count", "total_s", "new_compiles"}.
    points     — metric -> list of (round, value) timeline samples.
    """

    events: List[dict]
    counters: Dict[str, float]
    gauges: Dict[str, float]
    histograms: Dict[str, List[float]]
    spans: Dict[str, dict]
    points: Dict[str, List[Tuple[int, float]]]

    @classmethod
    def from_events(cls, events: List[dict]) -> "TelemetrySnapshot":
        counters: Dict[str, float] = {}
        gauges: Dict[str, float] = {}
        hists: Dict[str, List[float]] = {}
        spans: Dict[str, dict] = {}
        points: Dict[str, List[Tuple[int, float]]] = {}
        for ev in events:
            kind, name = ev["kind"], ev["name"]
            if kind == "counter":
                counters[name] = counters.get(name, 0) + ev["value"]
            elif kind == "gauge":
                gauges[name] = ev["value"]
            elif kind == "hist":
                hists.setdefault(name, []).append(ev["value"])
            elif kind == "span_end":
                agg = spans.setdefault(
                    name, {"count": 0, "total_s": 0.0, "new_compiles": 0})
                agg["count"] += 1
                agg["total_s"] += ev["value"]
                agg["new_compiles"] += ev.get("new_compiles", 0)
            elif kind == "point":
                points.setdefault(name, []).append(
                    (ev["round"], ev["value"]))
        return cls(events=events, counters=counters, gauges=gauges,
                   histograms=hists, spans=spans, points=points)

    def counter(self, name: str, **tags) -> float:
        """Accumulated total of one counter restricted to matching tags.

        ``counters[name]`` aggregates across every tag combination; this
        accessor sums only increments whose tags include every given
        ``key=value`` pair — how the serving tier's tests read per-tenant
        and per-rejection-reason admission counts out of one registry
        (e.g. ``snap.counter("serve.rejected", reason="budget_exhausted")``).
        """
        total = 0.0
        for ev in self.events:
            if ev["kind"] != "counter" or ev["name"] != name:
                continue
            evt = ev.get("tags") or {}
            if all(evt.get(k) == v for k, v in tags.items()):
                total += ev["value"]
        return total

    def timeline(self, metric: str) -> Tuple[np.ndarray, np.ndarray]:
        """(rounds, values) arrays for one recorded timeline metric."""
        if metric not in self.points:
            raise KeyError(
                f"no timeline recorded for {metric!r}; have "
                f"{sorted(self.points)}")
        pts = self.points[metric]
        return (np.asarray([r for r, _ in pts], dtype=np.int64),
                np.asarray([v for _, v in pts], dtype=np.float64))


def make_recorder(spec) -> "Recorder | NullRecorder":
    """The recorder for a plan's telemetry declaration: the shared
    :data:`NULL_RECORDER` when ``spec`` is None/falsy, a live
    :class:`Recorder` otherwise. Accepts an existing recorder unchanged
    (so simulators can share a session's recorder)."""
    if spec is None or spec is False:
        return NULL_RECORDER
    if isinstance(spec, (Recorder, NullRecorder)):
        return spec
    if isinstance(spec, dict):
        spec = TelemetrySpec.from_dict(spec)
    if not isinstance(spec, TelemetrySpec):
        raise TypeError(f"expected TelemetrySpec, Recorder, or None; got "
                        f"{type(spec).__name__}")
    return Recorder(spec)
