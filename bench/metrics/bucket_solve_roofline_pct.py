"""Share of the roofline reached by the local Newton solves: the least
time the chip needs for their work (bench/work.py: true degrees, n, C and
the Newton iterations each bucket used, from the program's
``engine.newton_iters`` telemetry) over the device time of every operation
of the bucket-solve programs (XLA module ``jit__solve_bucket``) in the
window."""
from bench.work import bucket_solve_work, roofline_pct

MODULE = "jit__solve_bucket"


def _iterations(snapshots, degrees):
    """Newton iterations of every node in every call: a node belongs to
    the bucket of the least padded degree (``deg_pad`` tag) >= its own."""
    per_node = []
    for snap in snapshots:
        by_pad = {}
        for ev in snap.events:
            if ev["kind"] == "hist" and ev["name"] == "engine.newton_iters":
                pad = int(ev["tags"]["deg_pad"])
                by_pad[pad] = max(by_pad.get(pad, 0), int(ev["value"]))
        if not by_pad:
            return None
        pads = sorted(by_pad)
        for deg in degrees:
            pad = next((q for q in pads if q >= deg), None)
            if pad is None:
                return None
            per_node.append(by_pad[pad])
    return per_node


def read(ctx):
    if ctx.trace is None or ctx.peak is None or not ctx.telemetry:
        return None
    device_s = ctx.trace.module_seconds(MODULE)
    if device_s <= 0:
        return None
    w = ctx.work
    iters = _iterations(ctx.telemetry, list(w["degrees"]))
    if iters is None:
        return None
    degrees = list(w["degrees"]) * len(ctx.telemetry)
    flops, nbytes = bucket_solve_work(degrees, iters, w["n"], w["C"],
                                      want_influence=w["want_influence"])
    return roofline_pct(flops, nbytes, device_s, ctx.peak)[0]
