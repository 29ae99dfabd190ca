"""Host-clock milliseconds per verb call in the pseudo-score of the
headline estimate (``score_norm``): the fused score kernel, its fetch to
the host and the gradient's assembly there; the program's ``score``
span."""
from bench.program import span_ms_per_call


def read(ctx):
    return span_ms_per_call(ctx, "score")
