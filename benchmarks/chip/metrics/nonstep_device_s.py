"""Device seconds per chunk outside the PIC step: `diagnostics()`'s
eager operations and the dump's copies, i.e. device busy time outside
`jit_pic_run_chunk` modules over the window's chunks."""
from benchmarks.chip import reduce_trace


def read(ctx):
    return (ctx.view.busy_outside(reduce_trace.PIC_MODULE)
            / ctx.counters["chunks"])
