"""Host-clock milliseconds per verb call inside the program's degree-bucket
solves: its ``bucket_solve`` and ``prox_bucket_solve`` telemetry spans
(each closes after its device sync), summed over the window's calls."""


def read(ctx):
    if not ctx.telemetry:
        return None
    total, seen = 0.0, False
    for snap in ctx.telemetry:
        for path, agg in snap.spans.items():
            leaf = path.rsplit("/", 1)[-1]
            if leaf in ("bucket_solve", "prox_bucket_solve"):
                total += agg["total_s"]
                seen = True
    return 1e3 * total / len(ctx.telemetry) if seen else None
