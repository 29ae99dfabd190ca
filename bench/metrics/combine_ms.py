"""Host-clock milliseconds per ``fit`` inside the program's one-step
combiners: its ``combine`` telemetry spans, one per requested combiner."""


def read(ctx):
    if not ctx.telemetry:
        return None
    total, seen = 0.0, False
    for snap in ctx.telemetry:
        for path, agg in snap.spans.items():
            if path.rsplit("/", 1)[-1] == "combine":
                total += agg["total_s"]
                seen = True
    return 1e3 * total / len(ctx.telemetry) if seen else None
