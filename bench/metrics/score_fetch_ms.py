"""Host-clock milliseconds per verb call in the score's fetch to the host:
the (n, p) residuals and the (p, p) Gram converted to float64 there, after
the kernel has finished; the program's ``score_fetch`` span, inside
``score``."""
from bench.program import span_ms_per_call


def read(ctx):
    return span_ms_per_call(ctx, "score_fetch")
