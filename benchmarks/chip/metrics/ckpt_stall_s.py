"""Seconds a save stalled its caller: `CheckpointManager.stats` over the
window, blocked seconds over saves."""


from benchmarks.chip import reduce_trace


def read(ctx):
    if not ctx.counters.get("saves"):
        raise reduce_trace.MissingSource("no save in the window "
                                         "(CheckpointManager.stats['saves'])")
    return ctx.counters["blocked_s"] / ctx.counters["saves"]
