"""Device seconds of one PIC step: the device time of the window's
`jit_pic_run_chunk` modules over the steps they made."""
from benchmarks.chip import reduce_trace


def read(ctx):
    return (ctx.view.module_time(reduce_trace.PIC_MODULE)
            / ctx.counters["steps"])
