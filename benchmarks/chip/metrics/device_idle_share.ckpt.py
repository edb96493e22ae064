"""Share, in percent, of the traced window in which no operation ran on the device."""


def read(ctx):
    return 100.0 * (1.0 - ctx.view.busy_s / ctx.view.window_s)
