"""Mean milliseconds from a request's due time to the start of the pump
call that served it (the benchmark's clock), over the window's served
requests."""


def read(ctx):
    waits = ctx.window.samples.get("queue_wait_s")
    if not waits:
        return None
    return 1e3 * sum(waits) / len(waits)
