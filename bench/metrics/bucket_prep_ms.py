"""Host-clock milliseconds per verb call preparing the degree buckets'
inputs (offset gather, sample weights, warm start, proximal terms): the
program's ``bucket_prep`` spans, one per bucket solve."""
from bench.program import span_ms_per_call


def read(ctx):
    return span_ms_per_call(ctx, "bucket_prep")
