"""Share of the traced window in which no operation ran on the device, in %."""


def read(ctx):
    red = ctx.red
    if red.window_ns <= 0:
        return None
    return 100.0 * (1.0 - red.busy_ns / red.window_ns)
