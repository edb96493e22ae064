"""Share, in percent, of the window's device-idle time in which no leaf
`jbp.*` span of the program (one with no other span inside it) is open on
any host thread: idle time no span of the work itself explains."""
from benchmarks.chip import program_spans


def read(ctx):
    return program_spans.idle_unattributed_share(ctx.view)
