"""Host seconds of one `series.drain()`: the benchmark's span around each
drain, averaged over the window's drains."""


def read(ctx):
    return ctx.view.span_mean("bench.drain")
