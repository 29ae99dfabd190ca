"""Host-clock milliseconds per verb call building the per-node local fits
from each bucket's fetched arrays: the program's ``assemble`` spans, one
per degree bucket."""
from bench.program import span_ms_per_call


def read(ctx):
    return span_ms_per_call(ctx, "assemble")
