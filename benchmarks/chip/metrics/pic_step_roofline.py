"""Share, in percent, of the memory roofline that a PIC step reaches: the least time
in which the chip's HBM moves the bytes one step must move
(`counts.pic_step_bytes`), over the step's device time. Memory bounds the
step: it does a few operations per byte moved."""
from benchmarks.chip import counts, peaks, reduce_trace


def read(ctx):
    least = (counts.pic_step_bytes(ctx.counters["capacity"])
             / peaks.peak(ctx.device_kind, "hbm_bytes_per_s"))
    step = (ctx.view.module_time(reduce_trace.PIC_MODULE)
            / ctx.counters["steps"])
    return 100.0 * least / step
