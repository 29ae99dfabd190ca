"""Mean size of the coalesced group each served request rode in, as the
program reports it (``ServeResult.coalesce_size``)."""


def read(ctx):
    sizes = ctx.window.samples.get("coalesce_sizes")
    if not sizes:
        return None
    return sum(sizes) / len(sizes)
