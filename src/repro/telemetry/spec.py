"""The frozen, serializable telemetry declaration a :class:`Plan` carries.

Like :class:`~repro.stream.faults.FaultPlan`, a :class:`TelemetrySpec` is a
plain hashable value object: it rides on the (frozen, hashable) plan, keys
session caches, and round-trips exactly through ``to_dict``/``from_dict``
so plans with telemetry still serialize into configs and benchmark JSON.
"""
from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass(frozen=True)
class TelemetrySpec:
    """Declarative telemetry configuration.

    spans — record hierarchical spans (``fit`` → bucket solve → kernel
        dispatch; ``stream`` → round → receive/refit/combine; ``joint`` →
        ADMM iteration) with wall time and compile-count deltas.
    metrics — record counters/gauges/histograms (comm scalars by scheme,
        buffer occupancy, window effective counts, fault injections fired,
        robust-combiner rejections, per-bucket Newton iterations).
    jsonl — path of an append-only JSONL event log (None = in-memory
        only). Replaying the log reconstructs the exact comm accounting
        (see :mod:`repro.telemetry.replay`).

    Every live span is also a ``jax.profiler`` annotation: wrap any call
    in ``jax.profiler.trace(dir)`` to see the spans on the device trace.
    """

    spans: bool = True
    metrics: bool = True
    jsonl: Optional[str] = None

    def __post_init__(self):
        if self.jsonl is not None and not isinstance(self.jsonl, str):
            raise TypeError(f"TelemetrySpec.jsonl must be a path string or "
                            f"None, got {type(self.jsonl).__name__}")

    # ------------------------------------------------------- serialization
    def to_dict(self) -> dict:
        """Plain-JSON form; exact inverse of :meth:`from_dict`."""
        return {"spans": self.spans, "metrics": self.metrics,
                "jsonl": self.jsonl}

    @classmethod
    def from_dict(cls, d: dict) -> "TelemetrySpec":
        """Inverse of :meth:`to_dict`; keys it does not know (such as the
        retired ``profile_dir``) are ignored."""
        return cls(spans=bool(d.get("spans", True)),
                   metrics=bool(d.get("metrics", True)),
                   jsonl=d.get("jsonl"))
