"""Device-to-host round trips per verb call on the estimate path: the
increments of the program's ``engine.d2h_bytes`` counter, one per fetch
group (a bucket solve's outputs, the score's residuals and Gram)."""
from bench.program import counter_per_call


def read(ctx):
    got = counter_per_call(ctx, "engine.d2h_bytes")
    return None if got is None else got[1]
