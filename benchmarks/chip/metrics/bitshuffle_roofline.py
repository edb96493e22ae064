"""Share, in percent, of the memory roofline that the byte-shuffle kernel reaches:
the least time in which the chip's HBM moves the bytes the window's
shuffles read and write (`counts.shuffle_bytes`), over the summed
device time of the kernel's events."""
from benchmarks.chip import counts, peaks, reduce_trace


def read(ctx):
    least = (counts.shuffle_bytes(ctx.counters["shuffled_bytes"])
             / peaks.peak(ctx.device_kind, "hbm_bytes_per_s"))
    return 100.0 * least / ctx.view.op_time(reduce_trace.SHUFFLE_KERNEL)
