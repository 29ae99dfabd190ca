"""Megabytes (1e6 bytes) fetched from the device to the host per verb
call on the estimate path: the program's ``engine.d2h_bytes`` counter,
the device arrays' bytes at each bucket-solve and score fetch."""
from bench.program import counter_per_call


def read(ctx):
    got = counter_per_call(ctx, "engine.d2h_bytes")
    return None if got is None else got[0] / 1e6
