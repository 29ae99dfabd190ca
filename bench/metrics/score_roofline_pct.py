"""Share of the roofline reached by the pseudo-score of each ``fit`` (its
``score_norm``): the least time the chip needs for a sparse pseudo-score
(bench/work.py: one read of the samples, 4 n (p + 2m) C operations) over
the device time of every operation of the score program (XLA module
``jit_cl_score_channels``) in the window."""
from bench.work import roofline_pct, score_work

MODULE = "jit_cl_score_channels"


def read(ctx):
    if ctx.trace is None or ctx.peak is None:
        return None
    device_s = ctx.trace.module_seconds(MODULE)
    w = ctx.work
    if device_s <= 0 or not w.get("calls"):
        return None
    flops, nbytes = score_work(w["n"], w["p"], w["m"], w["C"])
    return roofline_pct(w["calls"] * flops, w["calls"] * nbytes, device_s,
                        ctx.peak)[0]
