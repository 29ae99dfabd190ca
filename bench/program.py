"""What the per-layer metrics read of the program's own telemetry: span
seconds and counter increments, per verb call of the window (one
``TelemetrySnapshot`` a call). Each returns None where no call recorded
what it reads, as a program without that span or counter does."""


def span_ms_per_call(ctx, leaf: str):
    """Milliseconds per call inside the spans whose path ends in ``leaf``."""
    if not ctx.telemetry:
        return None
    total, seen = 0.0, False
    for snap in ctx.telemetry:
        for path, agg in snap.spans.items():
            if path.rsplit("/", 1)[-1] == leaf:
                total += agg["total_s"]
                seen = True
    return 1e3 * total / len(ctx.telemetry) if seen else None


def counter_per_call(ctx, name: str):
    """(summed value, number of increments) of counter ``name`` per call."""
    if not ctx.telemetry:
        return None
    total, count = 0.0, 0
    for snap in ctx.telemetry:
        for ev in snap.events:
            if ev["kind"] == "counter" and ev["name"] == name:
                total += ev["value"]
                count += 1
    calls = len(ctx.telemetry)
    return (total / calls, count / calls) if count else None
