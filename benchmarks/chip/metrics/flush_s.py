"""Host seconds of one `series.flush()`: the benchmark's span around each
flush, averaged over the window's flushes."""


def read(ctx):
    return ctx.view.span_mean("bench.flush")
